"""Router (``models/zaya.router``, ``balance_step``): device time of one step under the scope ``router``, inside ``moe``: the projection to the router's 256-wide state, the carry from the previous layer, the MLP, the softmax, ``argmax(p + beta)``, the gate, the loads and the biases' update, forward, rematerialised and backward; the sort, the grouped products and the combine stay under ``moe``. Own time by layer (``lib/layer_account``), first device."""

from lib import layer_account


def read(ctx):
    return layer_account.layer_ms_per_step(ctx, "router")
