"""Sparse path: the part of a step in which the core itself sat in a
collective operation (a synchronous one, or the wait in an asynchronous
one's ``-done``) and ran nothing else, averaged over the chips: what
the hybrid plan costs a step that one chip does not pay."""

from reduce import xplane


def read(ctx):
    seconds = ctx.mean_over_devices(
        lambda ops, lo, hi: xplane.exposed_seconds(ops, "collective",
                                                   lo, hi))
    return ctx.per_step_ms(seconds, "train_step", "engine.step")
