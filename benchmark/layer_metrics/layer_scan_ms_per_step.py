"""Step (``models/keye_vl2`` ``loss_fn``'s ``lax.scan`` over the blocks): device time of one step under the scope ``layer_scan`` and under no block's: what the scan itself costs: a layer's weights cut out of the stacked arrays, the arrays a rematerialised layer keeps and its gradients written into theirs, the zeros they start from, the loop's own time. Own time by layer (``lib/layer_account``), first device."""

from lib import layer_account


def read(ctx):
    return layer_account.layer_ms_per_step(ctx, "layer_scan")
