"""Attention: the dense flash forward kernel's (online softmax over the
causal keys; output and logsumexp) share of its roofline, %: the least
time the chip could take for the model's work, two products over each
query's causal keys (``kernels/causal_attn.cost``), over the time of the
Mosaic calls named ``flash_fwd.N`` under the scope ``attention`` (first
device). A program without such calls reads nothing."""

from kernels import causal_attn, roofline
from lib import kernel_calls


def share(ctx, kernels: tuple, products: int, rows: int):
    """The share of the calls named by ``kernels`` together:
    ``products`` matrix products over the causal pairs, ``rows`` arrays
    of the queries' side read or written a pass; a pass is one call of
    the first of ``kernels``. The queries a block are the cell's own
    (``model.flash_tiles``, what the program's kernels are given): they
    decide how often a key/value tile is read, a tenth of a
    compute-bound kernel's floor."""
    calls = [kernel_calls.per_step(ctx, "attention", k) for k in kernels]
    m = ctx.cell.model
    if (any(c is None for c in calls) or ctx.peaks is None
            or "flash_tiles" not in m):
        return None
    cost = causal_attn.cost(
        T=int(m["seq_len"]), Hq=int(m["num_heads"]),
        Hkv=int(m["num_kv_heads"]), D=int(m["head_dim"]),
        block=int(m["flash_tiles"][0]), products=products, rows=rows,
        passes=calls[0][1])
    return roofline.share_percent(cost, ctx.peaks,
                                  sum(seconds for seconds, _ in calls))


def read(ctx):
    return share(ctx, ("flash_fwd",), products=2, rows=2)
