"""Experts: the weight-gradient products' share of their roofline, %:
``kernels/moe_tgmm.cost`` for the rows routed here over the time of the
Mosaic calls named ``tgmm.N`` under ``moe`` (first device), read as
``moe_gmm_roofline`` reads its own."""

from kernels import moe_tgmm


def read(ctx):
    gmm = ctx.cell.plugin("layer_metrics", "moe_gmm_roofline")
    return gmm.share(ctx, "tgmm", moe_tgmm.cost)
