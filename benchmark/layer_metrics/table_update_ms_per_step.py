"""Sparse path: device time of one step under the scope
``table_update`` (``core/engine.train_step`` around ``ops/sparse_optim``):
combining the duplicate rows of a step's ids, and the row scatter into
each table and its Adagrad accumulator. Own time by layer
(``lib/layer_account``), first device."""

from lib import layer_account


def read(ctx):
    return layer_account.layer_ms_per_step(ctx, "table_update")
