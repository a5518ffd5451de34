"""Linear attention: the gated delta rule's backward kernel's share of
its roofline, %: twice the forward's products and the operands, ``do``
and the five cotangents moved once (``kernels/delta_rule.cost``), over
the time of the Mosaic calls named ``delta_bwd.N`` under ``delta_rule``,
read as ``delta_rule_fwd_roofline`` reads its own (the kernel makes the
chunk's forward products again from the kept state and system: eleven
more products for the sixteen the model needs)."""


def read(ctx):
    fwd = ctx.cell.plugin("layer_metrics", "delta_rule_fwd_roofline")
    return fwd.share(ctx, "delta_bwd", backward=True)
