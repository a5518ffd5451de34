"""Sparse path: time of one step in which a collective operation
(all-reduce, all-gather, reduce-scatter, all-to-all,
collective-permute) was running or in flight on a device, averaged over
the chips: the union of the collectives on the core's own stream and of
the asynchronous ones from their start to their done. Reads 0 on one
chip."""

from lib import stats
from reduce import xplane


def read(ctx):
    devs = ctx.device_ops()
    if not devs:
        return None
    lo, hi = ctx.window
    seconds = sum(
        stats.total(stats.merge_intervals(
            xplane.category_intervals(ops, "collective", lo, hi)
            + xplane.category_intervals(ctx.async_ops(d), "collective",
                                        lo, hi)))
        for d, ops in devs) / len(devs)
    return ctx.per_step_ms(seconds, "train_step", "engine.step")
