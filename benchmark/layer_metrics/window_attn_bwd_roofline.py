"""Attention under a window: the windowed flash backward kernels' share
of their roofline, %: five products over each query's ``window`` keys
(``kernels/banded_attn.cost``: the logits again, ``do v^T``, and dv, dk,
dq) over the time of the Mosaic calls named ``flash_dq_win.N`` and
``flash_dkv_win.N`` under ``window_attention`` together (each makes the
logits and ``do v^T`` for itself: seven products run for the five the
model needs), read as ``window_attn_fwd_roofline`` reads its own."""


def read(ctx):
    fwd = ctx.cell.plugin("layer_metrics", "window_attn_fwd_roofline")
    return fwd.share(ctx, ("flash_dq_win", "flash_dkv_win"), products=5,
                     rows=4)
