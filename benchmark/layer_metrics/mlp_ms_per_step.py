"""Dense MLP (``models/olmo_hybrid.mlp``): device time of one step under the scope ``mlp``: the SwiGLU's three products of every layer, forward, rematerialised and backward; the norm behind it stays with ``layer_scan``. Own time by layer (``lib/layer_account``), first device."""

from lib import layer_account


def read(ctx):
    return layer_account.layer_ms_per_step(ctx, "mlp")
