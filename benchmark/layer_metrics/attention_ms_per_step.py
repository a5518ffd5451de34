"""Attention (``models/keye_vl2._layer``, ``ops/sparse_attention``): device time of one step under the scope ``attention``: the q, k, v and output products, QK-norm, RoPE, and the attention over the selected keys, forward, rematerialised and backward; the indexer inside it goes by its own scope. Own time by layer (``lib/layer_account``), first device."""

from lib import layer_account


def read(ctx):
    return layer_account.layer_ms_per_step(ctx, "attention")
