"""Experts (``ops/moe.routed_experts`` under ``models/keye_vl2._layer``): device time of one step under the scope ``moe``: the norm, the router, sorting the rows by expert, the grouped products (the ``gmm`` / ``tgmm`` kernels are their part), the combine and the auxiliary loss. Own time by layer (``lib/layer_account``), first device."""

from lib import layer_account


def read(ctx):
    return layer_account.layer_ms_per_step(ctx, "moe")
