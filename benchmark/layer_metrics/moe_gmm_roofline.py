"""Experts: the grouped products' share of their roofline, %: the least
time the chip could take for the operations and bytes of the rows
routed here (``kernels/moe_gmm.cost``; the rows from the gauge
``moe.rows_here``, the last step's), over the time of the Mosaic calls
named ``gmm.N`` that the program's ``layer_index()`` holds under ``moe``
(first device). A program without such calls reads nothing."""

from kernels import moe_gmm, roofline
from lib import kernel_calls


def share(ctx, prefix: str, cost_fn):
    calls = kernel_calls.per_step(ctx, "moe", prefix)
    rows = ctx.run["registry_after"].get("moe.rows_here")
    if calls is None or not rows or ctx.peaks is None:
        return None
    seconds, per_step = calls
    model = ctx.cell.model
    cost = cost_fn(rows=float(rows), held=int(model["experts_held"]),
                   D=int(model["model_dim"]), F=int(model["expert_dim"]),
                   calls=per_step)
    return roofline.share_percent(cost, ctx.peaks, seconds)


def read(ctx):
    return share(ctx, "gmm", moe_gmm.cost)
