"""Attention under a window: the windowed flash forward kernel's share
of its roofline, %: the least time the chip could take for the model's
work, two products over each query's ``window`` keys
(``kernels/banded_attn.cost``), over the time of the Mosaic calls named
``flash_fwd_win.N`` under the scope ``window_attention`` (first device):
the window layers' calls alone, the full layers' carry other names under
``attention`` and are ``flash_attn_fwd_roofline``'s. A program without
such calls reads nothing."""

from kernels import banded_attn, roofline
from lib import kernel_calls


def share(ctx, kernels: tuple, products: int, rows: int):
    """The share of the calls named by ``kernels`` together:
    ``products`` matrix products over the attended pairs, ``rows``
    arrays of the queries' side read or written a pass; a pass is one
    call of the first of ``kernels``. The queries a block are the
    cell's own (``model.flash_tiles``: the windowed calls take the
    tiles of the plain ones)."""
    calls = [kernel_calls.per_step(ctx, "window_attention", k)
             for k in kernels]
    m = ctx.cell.model
    tiles = m.get("flash_tiles")
    if (any(c is None for c in calls) or ctx.peaks is None or not tiles
            or "sliding_window" not in m):
        return None
    cost = banded_attn.cost(
        T=int(m["seq_len"]), W=int(m["sliding_window"]),
        Hq=int(m["num_heads"]), Hkv=int(m["num_kv_heads"]),
        D=int(m["head_dim"]), block=int(tiles[0]), products=products,
        rows=rows, passes=calls[0][1])
    return roofline.share_percent(cost, ctx.peaks,
                                  sum(seconds for seconds, _ in calls))


def read(ctx):
    return share(ctx, ("flash_fwd_win",), products=2, rows=2)
