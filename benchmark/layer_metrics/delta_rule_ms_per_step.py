"""The gated delta rule (``ops/delta_rule.gated_delta_rule``): device time of one step under the scope ``delta_rule``, inside ``linear_attention``: the Mosaic calls ``delta_fwd`` and ``delta_bwd`` of the linear layers, and what their call site does around them (the operands brought to ``[B, H, chunk, C, d]``, the running decay's sums, the kept states written into the layers' stacks). Own time by layer (``lib/layer_account``), first device."""

from lib import layer_account


def read(ctx):
    return layer_account.layer_ms_per_step(ctx, "delta_rule")
