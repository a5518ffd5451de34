"""Compile layer: seconds of set-up inside jax's backend compile, which
is XLA compiling OR the persistent cache handing the executable back
(``compile.backend_s`` when set-up ended; the event wraps both). Beside
``setup_cache_load_s`` and ``setup_cache_misses`` it says which."""


def read(ctx):
    return ctx.run["registry_before"].get("compile.backend_s")
