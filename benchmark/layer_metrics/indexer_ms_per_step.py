"""Indexer (``ops/sparse_attention._chunk``, ``models/keye_vl2._layer``): device time of one step under the scope ``indexer``: its three projections, the scores of every causal key, the exact top-k selection (the threshold search and the tie count), and its KL loss, forward, rematerialised and backward. Own time by layer (``lib/layer_account``), first device."""

from lib import layer_account


def read(ctx):
    return layer_account.layer_ms_per_step(ctx, "indexer")
