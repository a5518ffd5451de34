"""Step layer: compilations inside the measured window
(``engine.recompiles`` after it less before it). Must read 0."""


def read(ctx):
    after = ctx.run["registry_after"].get("engine.recompiles", 0)
    before = ctx.run["registry_before"].get("engine.recompiles", 0)
    return int(after) - int(before)
