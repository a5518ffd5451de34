"""Attention under a window (``ops/pallas_attention``'s windowed calls): device time of one step under the scope ``window_attention``, inside ``attention``: the three flash kernels of the layers that attend under the window (``flash_fwd_win``, ``flash_dq_win``, ``flash_dkv_win``) and what their call sites reshape around them; the projections, norms, RoPE, the output product and the full layers' kernels stay under ``attention``. Own time by layer (``lib/layer_account``), first device."""

from lib import layer_account


def read(ctx):
    return layer_account.layer_ms_per_step(ctx, "window_attention")
