"""Attention: the dense flash backward kernels' share of their roofline,
%: five products over each query's causal keys
(``kernels/causal_attn.cost``: the logits again, ``do v^T``, and dv, dk,
dq) over the time of the Mosaic calls named ``flash_dq.N`` and
``flash_dkv.N`` under ``attention`` together (each makes the logits and
``do v^T`` for itself: seven products run for the five the model needs),
read as ``flash_attn_fwd_roofline`` reads its own."""


def read(ctx):
    fwd = ctx.cell.plugin("layer_metrics", "flash_attn_fwd_roofline")
    return fwd.share(ctx, ("flash_dq", "flash_dkv"), products=5, rows=4)
