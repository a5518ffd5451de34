"""Attention, the indexer: the share of its roofline of the backward
kernel of the indexer's scores, %: the least time the chip could take
for the model's work, three products a head over each query's selected
keys (``kernels/indexer.cost``), over the time of the calls named
``indexer_bwd.N`` under the scope ``indexer``, found as
``indexer_kernel_ms_per_step`` finds them (first device). The kernel
walks every causal tile, so its share of the chip's peak lies above
this. A program without the kernel reads nothing."""

from kernels import indexer, roofline


def read(ctx):
    calls = ctx.cell.plugin(
        "layer_metrics", "indexer_kernel_ms_per_step").calls(ctx)
    if calls is None or ctx.peaks is None:
        return None
    seconds, per_step = calls
    m = ctx.cell.model
    T, chunk = int(m["seq_len"]), int(m["q_chunk_size"])
    cost = indexer.cost(
        T=T, Hi=int(m["indexer_heads"]), Di=int(m["indexer_head_dim"]),
        topk=int(m["indexer_topk"]), chunk=chunk,
        passes=per_step / -(-T // chunk))
    return roofline.share_percent(cost, ctx.peaks, seconds)
