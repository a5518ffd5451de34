"""CCA mixing (``models/zaya.cca_mix``): device time of one step under the scope ``cca_mix``, inside ``attention``: the down-projections to the query and key latents, the shifted value, both causal convolutions, the q-k mean, the heads' normalisation with its temperature, and RoPE, forward, rematerialised and backward; the flash kernels and the output product stay under ``attention``. Own time by layer (``lib/layer_account``), first device."""

from lib import layer_account


def read(ctx):
    return layer_account.layer_ms_per_step(ctx, "cca_mix")
