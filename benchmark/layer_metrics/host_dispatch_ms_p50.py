"""Entry layer: the host's time inside one step's dispatch, median of
the program's rolling ``pipeline.dispatch_ms`` at the end of the
window. Under asynchronous dispatch it is hidden until, with the data
wait, it exceeds the device's step. (``pipeline.dispatch_gap_ms`` is
not read: the gap between two dispatches holds the loop's own wait for
the loss of four steps ago, so it reads as the device's step.)"""


def read(ctx):
    hist = ctx.run["registry_after"].get("pipeline.dispatch_ms")
    return hist["p50"] if hist else None
