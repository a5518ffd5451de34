"""LSTM kernels: device time of a step's Mosaic kernels (custom calls
whose target is ``tpu_custom_call``), averaged over the chips. The
compiled LM1B step holds exactly two (the recurrence forward and its
backward; the trace of PR 23 names them ``jvp__`` and
``transpose_jvp___``), so the Mosaic calls of the trace are the two
kernels."""

from reduce import xplane


def read(ctx):
    seconds = ctx.mean_over_devices(
        lambda ops, lo, hi: xplane.category_seconds(ops, "mosaic",
                                                    lo, hi))
    return ctx.per_step_ms(seconds, "train_step", "engine.step")
