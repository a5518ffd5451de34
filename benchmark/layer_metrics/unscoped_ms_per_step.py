"""Step layer: device time of one step under no layer's scope: the
model's own glue (dropout's random bits, transposes, the loss's mean)
and the compiler's copies, plus what the index does not hold at all
(``unknown``, which must be 0: index and trace are of one executable).
Own time by layer (``lib/layer_account``), first device."""

from lib import layer_account


def read(ctx):
    return layer_account.layer_ms_per_step(ctx, layer_account.UNSCOPED,
                                           layer_account.UNKNOWN)
