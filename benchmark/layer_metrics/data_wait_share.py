"""Entry layer: the share of the traced window the dispatch thread
spent waiting for its next batch (``session.data_wait`` spans), %."""


def read(ctx):
    if not ctx.traced_seconds:
        return None
    waits = ctx.spans_named("session.data_wait")
    return 100.0 * sum(s["end"] - s["start"] for s in waits) \
        / ctx.traced_seconds
