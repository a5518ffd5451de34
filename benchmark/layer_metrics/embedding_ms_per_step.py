"""Sparse path: device time of one step under the scope ``embedding``
(``ops/embedding.embedding_lookup``): the row gathers of the input
table and of the sampled softmax's candidate rows (the innermost scope
wins), the exchange under sharding, the slices' gradient rows. Own time
by layer (``lib/layer_account``), first device."""

from lib import layer_account


def read(ctx):
    return layer_account.layer_ms_per_step(ctx, "embedding")
