"""Compile layer: seconds jax spent tracing, lowering or compiling
ANYTHING inside the measured window (the window's rise of
``compile.trace_s`` + ``compile.lower_s`` + ``compile.backend_s``).
Must read 0. ``recompiles_in_window`` sees only the engine's new batch
signatures; this sees every jit of the process."""

NAMES = ("compile.trace_s", "compile.lower_s", "compile.backend_s")


def read(ctx):
    before, after = ctx.run["registry_before"], ctx.run["registry_after"]
    parts = [reg.get(name) for reg in (before, after) for name in NAMES]
    if None in parts:
        return None
    return sum(parts[len(NAMES):]) - sum(parts[:len(NAMES)])
