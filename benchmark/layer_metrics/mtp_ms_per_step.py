"""The multi-token-prediction blocks' input (``models/glm4_moe_lite.mtp_input``): device time of one step under the scope ``mtp``: the next token's embedding and the stream normed and joined, and the product ``W_eh`` that brings them back to the model's width, forward and backward; the block's own attention, experts and head stay under ``attention``, ``moe`` and ``lm_head``. Own time by layer (``lib/layer_account``), first device. A program without the scope reads nothing."""

from lib import layer_account


def read(ctx):
    return layer_account.layer_ms_per_step(ctx, "mtp")
