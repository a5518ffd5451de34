"""Linear attention (``models/olmo_hybrid.linear_mixer``): device time of one step under the scope ``linear_attention``: a linear layer's q, k, v, gate and output products, the three short convolutions, the L2 norms, the two gates, the gated norm, forward, rematerialised and backward; the rule itself goes by its own scope ``delta_rule`` inside. Own time by layer (``lib/layer_account``), first device."""

from lib import layer_account


def read(ctx):
    return layer_account.layer_ms_per_step(ctx, "linear_attention")
