"""Step layer: the time from one step's dispatch to the next, median
over the traced window (``session.dispatch`` spans). The loop reads
each loss a few steps behind, so in a steady window this is the
device's step."""

from lib import stats


def read(ctx):
    starts = sorted(s["start"] for s in ctx.spans_named("session.dispatch"))
    gaps = [1e3 * (b - a) for a, b in zip(starts, starts[1:])]
    return stats.median(gaps)
