"""Attention: the forward kernel's (online softmax over the selected
keys; output and logsumexp) share of its roofline, %: the least time the
chip could take for the model's work, the products over each query's
selected keys (``kernels/sparse_attn.cost``), over the time of the
Mosaic calls named ``sparse_attn_fwd.N`` under the scope ``attention``
(first device). The kernel walks every causal tile under the mask, so
its share of the chip's peak lies above this."""

from kernels import roofline, sparse_attn
from lib import kernel_calls


def share(ctx, kernel: str, products: int, rows: int, probs_out=False):
    """The share of ``sparse_attn_<kernel>``'s calls: ``products``
    matrix products over the selected pairs, ``rows`` arrays of the
    queries' side read or written a pass."""
    calls = kernel_calls.per_step(ctx, "attention", "sparse_attn_" + kernel)
    if calls is None or ctx.peaks is None:
        return None
    seconds, per_step = calls
    m = ctx.cell.model
    T, chunk = int(m["seq_len"]), int(m["q_chunk_size"])
    cost = sparse_attn.cost(
        T=T, Hq=int(m["num_heads"]), Hkv=int(m["num_kv_heads"]),
        D=int(m["head_dim"]), topk=int(m["indexer_topk"]), chunk=chunk,
        products=products, rows=rows, passes=per_step / -(-T // chunk),
        probs_out=probs_out)
    return roofline.share_percent(cost, ctx.peaks, seconds)


def read(ctx):
    return share(ctx, "fwd", products=2, rows=2)
