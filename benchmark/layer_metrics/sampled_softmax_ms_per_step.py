"""Sparse path: device time of one step under the scope
``sampled_softmax`` (``ops/sampled_softmax.sampled_softmax_loss``):
drawing the candidates, the dense products over them forward and
backward, the loss; not the candidate rows' gathers, which are
``embedding``'s. Own time by layer (``lib/layer_account``), first
device."""

from lib import layer_account


def read(ctx):
    return layer_account.layer_ms_per_step(ctx, "sampled_softmax")
