"""Attention: the share of its roofline, %, of the kernel that sums the
heads' probabilities (the indexer's target): one product over each
query's selected keys and a float32 ``[queries, causal keys]`` result
(``kernels/sparse_attn.cost``) over the time of the Mosaic calls named
``sparse_attn_probs.N`` under ``attention``, read as
``sparse_attn_fwd_roofline`` reads its own."""


def read(ctx):
    fwd = ctx.cell.plugin("layer_metrics", "sparse_attn_fwd_roofline")
    return fwd.share(ctx, "probs", products=1, rows=1, probs_out=True)
