"""Linear attention: the gated delta rule's forward kernel's share of
its roofline, %: the least time the chip could take for the MODEL's
work, the chunked algorithm's eight products a chunk at the paper's
chunk of 64 and its operands moved once (``kernels/delta_rule.cost``), over
the time of the Mosaic calls named ``delta_fwd.N`` under the scope
``delta_rule`` (first device). A program without such calls reads
nothing."""

from kernels import delta_rule, roofline
from lib import kernel_calls


def share(ctx, kernel: str, backward: bool):
    """The share of the calls named ``kernel``; a pass is one call. The
    heads are the held ones (``model.num_heads``), the chunk the
    count's own whatever the program's kernels take."""
    calls = kernel_calls.per_step(ctx, "delta_rule", kernel)
    m = ctx.cell.model
    if calls is None or ctx.peaks is None:
        return None
    seconds, passes = calls
    cost = delta_rule.cost(
        T=int(m["seq_len"]), H=int(m["num_heads"]),
        dk=int(m["linear_key_head_dim"]), dv=int(m["linear_value_head_dim"]),
        passes=passes, backward=backward)
    return roofline.share_percent(cost, ctx.peaks, seconds)


def read(ctx):
    return share(ctx, "delta_fwd", backward=False)
