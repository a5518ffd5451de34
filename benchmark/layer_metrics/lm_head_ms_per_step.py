"""Head (``models/keye_vl2`` ``loss_fn``): device time of one step under the scope ``lm_head``: the final norm, the product with the vocabulary slice, the cross-entropy, and their backward. Own time by layer (``lib/layer_account``), first device."""

from lib import layer_account


def read(ctx):
    return layer_account.layer_ms_per_step(ctx, "lm_head")
