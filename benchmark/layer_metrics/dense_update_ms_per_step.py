"""Step layer: device time of one step under the scope ``dense_update``
(``core/engine.train_step`` around ``tx.update`` and ``apply_updates``):
the clip and Adagrad on the dense group. Own time by layer
(``lib/layer_account``), first device."""

from lib import layer_account


def read(ctx):
    return layer_account.layer_ms_per_step(ctx, "dense_update")
