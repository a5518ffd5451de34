"""Latent attention's low-rank paths (``models/glm4_moe_lite.attention``): device time of one step under the scope ``mla_latent``, inside ``attention``: the query's two products and their norm, the keys' and values' latent product, its norm and its product back up per head, RoPE on the query's and the shared key's rotary dims and that key's broadcast to every head, forward and backward (and whatever of the forward the layer's remat makes again); the input norm, the flash kernels and ``Wo`` stay under ``attention``. Own time by layer (``lib/layer_account``), first device. A program without the scope reads nothing."""

from lib import layer_account


def read(ctx):
    return layer_account.layer_ms_per_step(ctx, "mla_latent")
