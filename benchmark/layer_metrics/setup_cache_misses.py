"""Compile layer: executables that set-up had to compile and write to
the persistent cache (``compile.cache_misses`` when set-up ended). 0
from a warm cache; over 0 marks the run's ``setup_s`` as a cold one."""


def read(ctx):
    return ctx.run["registry_before"].get("compile.cache_misses")
