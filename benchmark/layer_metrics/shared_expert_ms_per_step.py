"""Shared expert (``ops/moe.shared_expert``): device time of one step under the scope ``shared_expert``, inside ``moe``: the SwiGLU every token takes beside its routed experts, three plain products over all rows, forward and backward (and whatever of the forward the layer's remat makes again); the router, the sort, the grouped products and the combine stay under ``router`` and ``moe``. Own time by layer (``lib/layer_account``), first device. A program without the scope reads nothing."""

from lib import layer_account


def read(ctx):
    return layer_account.layer_ms_per_step(ctx, "shared_expert")
