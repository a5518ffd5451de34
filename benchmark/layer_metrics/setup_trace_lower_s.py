"""Compile layer: seconds of set-up that jax spent tracing jitted
functions and lowering them to MLIR, Pallas kernels to Mosaic included
(``compile.trace_s`` + ``compile.lower_s`` when set-up ended: jax's own
events, summed by the program over the PROCESS, each second once). All
of it is Python on the host, and a warm compile cache saves none of
it."""


def read(ctx):
    before = ctx.run["registry_before"]
    parts = [before.get("compile.trace_s"), before.get("compile.lower_s")]
    return None if None in parts else sum(parts)
