"""Attention's gate (``models/trinity.attention``): device time of one step under the scope ``attn_gate``, inside ``attention``: the product ``a Wg`` (as wide as the queries') and ``o * sigmoid(g)`` between the flash kernels' output and ``Wo``, forward and backward (and whatever of the forward the layer's remat makes again); the other projections, the norms, RoPE and the kernels stay under ``attention`` and ``window_attention``. Own time by layer (``lib/layer_account``), first device. A program without the scope reads nothing."""

from lib import layer_account


def read(ctx):
    return layer_account.layer_ms_per_step(ctx, "attn_gate")
