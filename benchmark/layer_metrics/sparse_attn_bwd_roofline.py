"""Attention: the backward kernel's (dk and dv of a key tile, dq
accumulated, one pass) share of its roofline, %: five products over each
query's selected keys (``kernels/sparse_attn.cost``) over the time of
the Mosaic calls named ``sparse_attn_bwd.N`` under ``attention``, read
as ``sparse_attn_fwd_roofline`` reads its own."""


def read(ctx):
    fwd = ctx.cell.plugin("layer_metrics", "sparse_attn_fwd_roofline")
    return fwd.share(ctx, "bwd", products=5, rows=4)
