"""Pallas flash-attention forward kernel (TPU).

The hot op of every transformer family here (NMT, BERT, long-context,
MoE-LM) is attention; this is its Pallas implementation: one fused kernel
per (batch, head, q-tile) program that streams K/V tiles through VMEM
with the online-softmax recurrence — the [Tq, Tk] score matrix never
exists in HBM.

Gradients: fully-Pallas backward — the forward kernel additionally emits
the per-row logsumexp; ONE backward kernel walks a query head's key
tiles, recomputes each tile's probabilities from (q, k, lse) once and
makes dq, dk and dv from them (the five products, each once): dk and dv
of the tile, dq of the whole head summed in a float32 scratch over the
key tiles, so the backward never materializes [Tq, Tk] either. Set
``xla_backward=True`` to fall back to the einsum-recompute backward.

Grouped key/value heads: ``q`` may bring ``g`` times the heads of ``k``
and ``v`` (query head ``h`` reads key/value head ``h // g``). No kernel
repeats K or V in HBM: both kernels walk the query heads in order and
map ``g`` consecutive ones onto one key/value head (the forward fetches
its block once for the group); the backward sums a group's ``dk`` and
``dv`` in float32 scratches of the whole key/value head, which leave
with the group's last query head. The two calls are named
``flash_fwd`` and ``flash_bwd``.

A window (``window=W`` with ``causal=True``): query ``t`` reads key
``s`` iff ``0 <= t - s < W``, the band behind the causal diagonal. The
kernels walk the band's tiles only: the forward loop starts at the
first key tile the query tile still sees, the backward loop ends at the
last query block that still sees the key tile, and each loop runs in
three consecutive ranges (``_key_ranges``, ``_query_ranges``): the
tiles on the band's edge (compared against both bounds), the tiles
wholly inside (no compare, no mask) and the diagonal's (the causal
compare, as without a window; without a window the backward's loop is
the last two ranges, so that only the diagonal's tiles pay for a
compare). Any positive ``W`` is legal. The windowed calls are named
``flash_fwd_win`` and ``flash_bwd_win`` and traced under the scope
``window_attention``, so that a trace tells them from a full layer's;
with a traced ``window_on`` a ``cond`` inside the ``custom_vjp``'s
forward and backward chooses between the two kinds' calls (four names
in one program, each once), and one loop body serves layers of both
kinds. Without a window nothing of this is traced.

On non-TPU backends the same kernels run in interpret mode (tests), so
numerics are validated everywhere the framework runs.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl

_NEG_INF = -1e30

# Per-row scalars (lse, delta) cross the pallas_call boundary broadcast
# over a trailing lane dimension: Mosaic requires the last two block
# dims to be (8k, 128m) or EQUAL to the array dims, so a [B, H, T]
# output with a per-(b, h) grid cannot be blocked legally — the r5 TPU
# lowering check caught exactly this (interpret mode hid it). The
# upstream kernel uses 128 lanes; 8 lanes satisfies the same rule via
# the equal-dims clause (the whole lane dim is one block) at 1/16 the
# HBM/VMEM cost of carrying a per-row scalar (r5 review). The public
# surface stays [B, H, T] (lane 0 sliced off / broadcast back at the
# boundary).
_LANES = 8

# what a caller's checkpoint policy keeps so that the forward kernel does
# not run twice: its output and logsumexp
KEPT = "flash_attn"

# the scope a windowed call sits under, forward and backward (one of
# ``obs/xprof.LAYER_SCOPES``: inside a model's ``attention`` it wins)
WINDOW_SCOPE = "window_attention"


def _tile_ok(q_pos, k_pos, diag: bool, band: bool, window):
    """Which (query, key) places of a tile are attended: not past the
    diagonal (``diag``) and less than ``window`` behind it (``band``)."""
    ok = None
    if diag:
        ok = q_pos >= k_pos
    if band:
        near = q_pos - k_pos < window
        ok = near if ok is None else ok & near
    return ok


def _key_ranges(q0, q_tile: int, block_k: int, window: int, num_k):
    """The key tiles of the query tile that starts at ``q0``, under a
    window, as three consecutive ranges ``[first, inside)``, ``[inside,
    diag)``, ``[diag, num_k)``: the band's edge (tiles some query of the
    tile sees in part only, compared against both bounds), the tiles
    every query sees whole (no compare) and the diagonal's (the causal
    compare). A window under a tile has no middle range: its tiles are
    all edge or diagonal."""
    first = jnp.maximum(q0 - window + 1, 0) // block_k
    inside = jnp.maximum(q0 + q_tile - window + block_k - 1, 0) // block_k
    inside = jnp.clip(inside, first, num_k)
    diag = jnp.clip((q0 + 1) // block_k, inside, num_k)
    return first, inside, diag


def _query_ranges(k0, k_tile: int, q_blk: int, window, q_lo, num_q):
    """The query blocks that see the key tile starting at ``k0`` under
    a causal diagonal: ``[q_lo, diag)`` on the diagonal (the causal
    compare), ``[diag, band)`` seeing it whole, ``[band, last)`` on a
    window's edge (both compares); ``last`` is the first block that sees
    none of it any more. Without a ``window`` every block past the
    diagonal sees the tile whole: ``band == last == num_q``."""
    if window is None:
        last = band = num_q
    else:
        last = jnp.minimum(num_q, (k0 + k_tile + window - 2) // q_blk + 1)
        band = jnp.clip((k0 + window + q_blk) // q_blk - 1, q_lo, last)
    diag = jnp.clip((k0 + k_tile + q_blk - 2) // q_blk, q_lo, band)
    return diag, band, last


def _flash_fwd_kernel(*refs, kv_len: int, block_k: int, causal: bool,
                      scale: float, q_tile: int, has_mask: bool,
                      window: Optional[int] = None):
    if has_mask:
        q_ref, k_ref, v_ref, mask_ref, o_ref, lse_ref = refs
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref = refs
        mask_ref = None
    # q_ref: [q_tile, D]; k_ref/v_ref: [Tk, D]; o_ref: [q_tile, D]
    qt = pl.program_id(2)
    q = q_ref[0, 0] * scale                                # [q_tile, D]
    D = q.shape[-1]

    m = jnp.full((q_tile,), _NEG_INF, jnp.float32)
    l = jnp.zeros((q_tile,), jnp.float32)
    acc = jnp.zeros((q_tile, D), jnp.float32)

    num_k = kv_len // block_k
    if causal:
        # K blocks entirely past this q-tile's diagonal are fully
        # masked — bound the loop instead of masking them
        num_k = jnp.minimum(
            num_k, ((qt + 1) * q_tile + block_k - 1) // block_k)

    def body(kt, carry, diag=causal, band=False, masked=True):
        m, l, acc = carry
        k_blk = k_ref[0, 0, pl.dslice(kt * block_k, block_k), :]
        v_blk = v_ref[0, 0, pl.dslice(kt * block_k, block_k), :]
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)             # [q_tile, bk]
        if mask_ref is not None:
            kv_ok = mask_ref[0, 0, pl.dslice(kt * block_k, block_k)]
            s = jnp.where(kv_ok[None, :] > 0, s, _NEG_INF)
        if diag or band:
            q_pos = qt * q_tile + jax.lax.broadcasted_iota(
                jnp.int32, (q_tile, block_k), 0)
            k_pos = kt * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (q_tile, block_k), 1)
            s = jnp.where(_tile_ok(q_pos, k_pos, diag, band, window), s,
                          _NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        alpha = jnp.exp(jnp.minimum(m - m_new, 0.0))
        p = jnp.exp(s - m_new[:, None])
        if masked:
            p = jnp.where(s > _NEG_INF / 2, p, 0.0)
        l = l * alpha + p.sum(axis=-1)
        acc = acc * alpha[:, None] + jax.lax.dot_general(
            p, v_blk.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    carry = (m, l, acc)
    if window is None:
        m, l, acc = jax.lax.fori_loop(0, num_k, body, carry)
    else:
        # the loop starts at the first key tile the query tile still
        # sees; only the tiles on the band's edge or on the diagonal pay
        # for a compare
        first, inside, diag = _key_ranges(qt * q_tile, q_tile, block_k,
                                          window, num_k)
        carry = jax.lax.fori_loop(
            first, inside, functools.partial(body, diag=True, band=True),
            carry)
        carry = jax.lax.fori_loop(
            inside, diag,
            functools.partial(body, diag=False, masked=has_mask), carry)
        m, l, acc = jax.lax.fori_loop(diag, num_k, body, carry)
    o_ref[0, 0] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)
    lse_ref[0, 0] = jax.lax.broadcast_in_dim(
        m + jnp.log(jnp.maximum(l, 1e-30)), (q_tile, _LANES), (0,))


def _sds(shape, dtype, like):
    """ShapeDtypeStruct carrying the caller's varying-mesh-axes set —
    required when the kernels run inside a shard_map (the ring
    attention block path); a plain struct elsewhere."""
    vma = jax.typeof(like).vma
    if vma:
        return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
    return jax.ShapeDtypeStruct(shape, dtype)


def _snap(tile, total):
    tile = min(tile, total)
    while total % tile:
        tile //= 2
    return max(tile, 1)


def _group(q, k) -> int:
    """Query heads a key/value head (``[B, H, T, D]`` layouts)."""
    Hq, Hkv = q.shape[1], k.shape[1]
    if Hq % Hkv:
        raise ValueError(f"{Hq} query heads do not group onto {Hkv} "
                         f"key/value heads")
    return Hq // Hkv


def _named(kernel: str, window) -> str:
    """The call's name: a windowed call carries ``_win`` behind it, so
    that a trace tells a window layer's kernels from a full layer's."""
    return kernel if window is None else kernel + "_win"


def _params(*semantics):
    from jax.experimental.pallas import tpu as pltpu
    # a whole key/value head (forward) or a query head's q, do and dq
    # (backward, with dq's float32 sum) stays in VMEM beside its second
    # buffer: the backward uses 41 MiB at 8,192 x 256, 33 MiB at 8,192 x
    # 128 in a group of 8 (the compiler's count, described v5e)
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=64 * 1024 * 1024)


def _by_kind(window, window_on, call):
    """``call(window)`` made for the kind of attention this call site
    runs: without a ``window`` the plain call; with one the windowed
    call under the scope ``window_attention``; with a traced
    ``window_on`` beside it a ``cond`` between the two, each built
    once."""
    if window is None:
        return call(None)

    def banded():
        with jax.named_scope(WINDOW_SCOPE):
            return call(window)

    if window_on is None:
        return banded()
    return jax.lax.cond(window_on, banded, lambda: call(None))


def _flash_forward(q, k, v, kv_mask, causal: bool, scale: float,
                   q_tile: int, block_k: int, interpret: bool,
                   window=None, window_on=None):
    """q: [B, Hq, T, D]; k, v: [B, Hkv, Tk, D]; kv_mask: [B, Tk] int32
    (1 = attendable). Returns (out [B, Hq, T, D], lse [B, Hq, T])."""
    def call(window):
        return _forward_call(q, k, v, kv_mask, causal, scale, q_tile,
                             block_k, interpret, window)
    return _by_kind(window, window_on, call)


def _forward_call(q, k, v, kv_mask, causal, scale, q_tile, block_k,
                  interpret, window):
    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    g = _group(q, k)
    q_tile = _snap(q_tile, Tq)
    block_k = _snap(block_k, Tk)
    grid = (B, H, Tq // q_tile)
    has_mask = kv_mask is not None
    kernel = functools.partial(
        _flash_fwd_kernel, kv_len=Tk, block_k=block_k, causal=causal,
        scale=scale, q_tile=q_tile, has_mask=has_mask, window=window)
    in_specs = [
        pl.BlockSpec((1, 1, q_tile, D), lambda b, h, i: (b, h, i, 0)),
        pl.BlockSpec((1, 1, Tk, D), lambda b, h, i: (b, h // g, 0, 0)),
        pl.BlockSpec((1, 1, Tk, D), lambda b, h, i: (b, h // g, 0, 0)),
    ]
    operands = [q, k, v]
    if has_mask:
        in_specs.append(pl.BlockSpec((1, 1, Tk),
                                     lambda b, h, i: (b, 0, 0)))
        operands.append(kv_mask[:, None, :])
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, q_tile, D),
                         lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, q_tile, _LANES),
                         lambda b, h, i: (b, h, i, 0)),
        ],
        out_shape=[
            _sds((B, H, Tq, D), q.dtype, q),
            _sds((B, H, Tq, _LANES), jnp.float32, q),
        ],
        compiler_params=_params("parallel", "parallel", "parallel"),
        name=_named("flash_fwd", window), interpret=interpret,
    )(*operands)
    return out, lse[..., 0]


def _flash_bwd_kernel(*refs, q_len: int, q_blk: int, causal: bool,
                      scale: float, k_tile: int, has_mask: bool,
                      group: int, window: Optional[int] = None):
    """``dq``, ``dk`` and ``dv`` of one key tile of one query head, from
    one pass over the tile's scores: the five products, each made once.
    The tile's scores are held transposed, ``[keys, queries]``, so that
    the per-query ``lse`` and ``delta`` broadcast along sublanes from
    lane-major rows. ``dq`` of the whole head adds up in a float32
    scratch over the key tiles, the innermost grid axis, and leaves
    after the last. Under a ``group`` of 1 a tile's ``dk`` and ``dv``
    leave at once; over it they add up in float32 scratches of the
    whole key/value head over its query heads, which the grid walks
    one after another, and leave with the group's last."""
    if has_mask:
        (q_ref, k_ref, v_ref, mask_ref, do_ref, lse_ref, delta_ref,
         dq_ref, dk_ref, dv_ref, dq_acc, *dkv_acc) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dk_ref,
         dv_ref, dq_acc, *dkv_acc) = refs
        mask_ref = None
    kt = pl.program_id(2)

    @pl.when(kt == 0)
    def _():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    k = k_ref[0, 0]                                        # [kt_, D]
    k32 = k.astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    num_q = q_len // q_blk
    # Q blocks entirely before this k-tile's diagonal see none of it
    q_lo = (kt * k_tile) // q_blk if causal else 0

    def body(qi, carry, diag=causal, band=False, masked=True):
        dk, dv = carry
        rows = pl.dslice(pl.multiple_of(qi * q_blk, q_blk), q_blk)
        q = q_ref[0, 0, rows, :] * scale
        do = do_ref[0, 0, rows, :].astype(jnp.float32)
        lse = lse_ref[0, 0, qi]                            # [1, qb]
        delta = delta_ref[0, 0, qi]
        st = jax.lax.dot_general(
            k, q, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)            # [kt_, qb]
        if mask_ref is not None:
            st = jnp.where(mask_ref[0][:, 0:1] > 0, st, _NEG_INF)
        if diag or band:
            k_pos = kt * k_tile + jax.lax.broadcasted_iota(
                jnp.int32, (k_tile, q_blk), 0)
            q_pos = qi * q_blk + jax.lax.broadcasted_iota(
                jnp.int32, (k_tile, q_blk), 1)
            st = jnp.where(_tile_ok(q_pos, k_pos, diag, band, window), st,
                           _NEG_INF)
        pt = jnp.exp(st - lse)
        if masked:
            pt = jnp.where(st > _NEG_INF / 2, pt, 0.0)
        dv = dv + jax.lax.dot_general(
            pt, do, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)            # [kt_, D]
        dpt = jax.lax.dot_general(
            v, do, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)            # [kt_, qb]
        dst = pt * (dpt - delta)
        dk = dk + jax.lax.dot_general(
            dst, q.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dq_acc[rows, :] = dq_acc[rows, :] + jax.lax.dot_general(
            dst, k32, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)            # [qb, D]
        return dk, dv

    tile = pl.dslice(pl.multiple_of(kt * k_tile, k_tile), k_tile)
    if group == 1:
        carry = (jnp.zeros(k.shape, jnp.float32),) * 2
    else:
        dk_acc, dv_acc = dkv_acc
        x = pl.program_id(1) % group

        @pl.when(x == 0)
        def _():
            dk_acc[tile, :] = jnp.zeros(k.shape, jnp.float32)
            dv_acc[tile, :] = jnp.zeros(k.shape, jnp.float32)
        carry = (dk_acc[tile, :], dv_acc[tile, :])
    if causal:
        # the blocks on the tile's diagonal pay for the causal compare,
        # those wholly past it for none; under a window the loop ends at
        # the last block that still sees the tile, its band's edge
        # compared against both bounds
        diag, band, last = _query_ranges(kt * k_tile, k_tile, q_blk, window,
                                         q_lo, num_q)
        carry = jax.lax.fori_loop(q_lo, diag, body, carry)
        carry = jax.lax.fori_loop(
            diag, band,
            functools.partial(body, diag=False, masked=has_mask), carry)
        if window is not None:
            carry = jax.lax.fori_loop(
                band, last, functools.partial(body, diag=True, band=True),
                carry)
    else:
        carry = jax.lax.fori_loop(
            0, num_q, functools.partial(body, masked=has_mask), carry)
    dk, dv = carry
    # q was pre-scaled, so dk absorbed one factor of `scale` already
    if group == 1:
        dk_ref[0, 0] = dk.astype(dk_ref.dtype)
        dv_ref[0, 0] = dv.astype(dv_ref.dtype)
    else:
        dk_acc[tile, :] = dk
        dv_acc[tile, :] = dv

        @pl.when(x == group - 1)
        def _():
            dk_ref[0, 0, tile, :] = dk.astype(dk_ref.dtype)
            dv_ref[0, 0, tile, :] = dv.astype(dv_ref.dtype)

    @pl.when(kt == pl.num_programs(2) - 1)
    def _():
        dq_ref[0, 0] = (dq_acc[...] * scale).astype(dq_ref.dtype)


def _flash_backward(q, k, v, kv_mask, out, lse, g, causal, scale,
                    q_tile, block_k, interpret, dlse=None, window=None,
                    window_on=None):
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)                               # [B, H, Tq]
    if dlse is not None:
        # lse cotangent folds into the existing kernel exactly:
        # d s = p*(dp - delta) + dlse*p = p*(dp - (delta - dlse))
        delta = delta - dlse.astype(jnp.float32)

    def call(window):
        return _backward_calls(q, k, v, kv_mask, lse, delta, g, causal,
                               scale, q_tile, block_k, interpret, window)
    return _by_kind(window, window_on, call)


def _backward_calls(q, k, v, kv_mask, lse, delta, g, causal, scale, q_tile,
                    block_k, interpret, window):
    from jax.experimental.pallas import tpu as pltpu
    B, H, Tq, D = q.shape
    Hkv, Tk = k.shape[1], k.shape[2]
    grp = _group(q, k)
    q_tile = _snap(q_tile, Tq)
    block_k = _snap(block_k, Tk)

    # grid (batch, query head, key tile): a query head's q, do and rows
    # are fetched once, the key tiles stream under them
    def of_q(b, h, j):
        return (b, h, 0, 0)

    def of_kv(b, h, j):
        return (b, h // grp, j, 0)

    has_mask = kv_mask is not None
    specs = [
        pl.BlockSpec((1, 1, Tq, D), of_q),
        pl.BlockSpec((1, 1, block_k, D), of_kv),
        pl.BlockSpec((1, 1, block_k, D), of_kv),
    ]
    operands = [q, k, v]
    if has_mask:
        # down the sublanes: the scores are held [keys, queries]
        specs.append(pl.BlockSpec((1, block_k, _LANES),
                                  lambda b, h, j: (b, j, 0)))
        operands.append(jnp.broadcast_to(
            kv_mask[:, :, None], (*kv_mask.shape, _LANES)))
    # ... and lse/delta along the lanes, one row a query block
    nq = Tq // q_tile
    rows = pl.BlockSpec((1, 1, nq, 1, q_tile), lambda b, h, j: (b, h, 0, 0, 0))
    specs += [pl.BlockSpec((1, 1, Tq, D), of_q), rows, rows]
    operands += [g, lse.reshape(B, H, nq, 1, q_tile),
                 delta.reshape(B, H, nq, 1, q_tile)]
    scratch = [pltpu.VMEM((Tq, D), jnp.float32)]
    if grp == 1:
        dkv_spec = pl.BlockSpec((1, 1, block_k, D), of_kv)
    else:
        # a key/value head's blocks stay while the grid walks its group
        dkv_spec = pl.BlockSpec((1, 1, Tk, D),
                                lambda b, h, j: (b, h // grp, 0, 0))
        scratch += [pltpu.VMEM((Tk, D), jnp.float32)] * 2
    return pl.pallas_call(
        functools.partial(_flash_bwd_kernel, q_len=Tq, q_blk=q_tile,
                          causal=causal, scale=scale, k_tile=block_k,
                          has_mask=has_mask, group=grp, window=window),
        grid=(B, H, Tk // block_k),
        in_specs=specs,
        out_specs=[pl.BlockSpec((1, 1, Tq, D), of_q), dkv_spec, dkv_spec],
        out_shape=[
            _sds((B, H, Tq, D), q.dtype, q),
            _sds((B, Hkv, Tk, D), k.dtype, k),
            _sds((B, Hkv, Tk, D), v.dtype, v),
        ],
        scratch_shapes=scratch,
        compiler_params=_params(
            "parallel", "parallel" if grp == 1 else "arbitrary",
            "arbitrary"),
        name=_named("flash_bwd", window), interpret=interpret,
    )(*operands)


def _repeat_kv(q, k, v):
    g = _group(q, k)
    if g == 1:
        return k, v
    return jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)


def _xla_scores(q, k, kv_mask, causal, scale, window=None):
    """Float32 scores ``[B, H, T, Tk]`` with everything unattended at
    ``_NEG_INF``; ``window`` may be a traced scalar."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q * scale, k,
                   preferred_element_type=jnp.float32)
    if kv_mask is not None:
        s = jnp.where(kv_mask[:, None, None, :] > 0, s, _NEG_INF)
    if causal:
        T, Tk = q.shape[2], k.shape[2]
        mask = jnp.tril(jnp.ones((T, Tk), bool))
        if window is not None:
            behind = jnp.arange(T)[:, None] - jnp.arange(Tk)[None, :]
            mask = mask & (behind < window)
        s = jnp.where(mask[None, None], s, _NEG_INF)
    return s


def _xla_attention(q, k, v, kv_mask, causal, scale, window=None):
    k, v = _repeat_kv(q, k, v)
    s = _xla_scores(q, k, kv_mask, causal, scale, window)
    p = jax.nn.softmax(s, axis=-1)
    # fully-masked rows: zero the uniform softmax so outputs and grads
    # match the Pallas kernels (which emit exact zeros there)
    p = jnp.where(s > _NEG_INF / 2, p, 0.0)
    return jnp.einsum("bhqk,bhkd->bhqd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


def _xla_window(window, window_on, Tk: int):
    """The window the einsum fallbacks mask by: none, the call's, or
    under a traced ``window_on`` the call's or every key."""
    if window is None or window_on is None:
        return window
    return jnp.where(window_on, window, Tk)


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(5, 6, 7, 8, 9, 10, 11))
def _flash_attention_masked(q, k, v, kv_mask, window_on, causal, scale,
                            q_tile, block_k, interpret, xla_backward, window):
    out, _ = _flash_forward(q, k, v, kv_mask, causal, scale, q_tile,
                            block_k, interpret, window, window_on)
    return out


def _fwd_masked(q, k, v, kv_mask, window_on, causal, scale, q_tile,
                block_k, interpret, xla_backward, window):
    out, lse = _flash_forward(q, k, v, kv_mask, causal, scale, q_tile,
                              block_k, interpret, window, window_on)
    # what a caller's checkpoint policy may keep for the backward pass
    # in place of running the forward kernel again
    out = checkpoint_name(out, KEPT)
    lse = checkpoint_name(lse, KEPT)
    return out, (q, k, v, kv_mask, window_on, out, lse)


def _no_cotangent(x):
    return None if x is None else np.zeros(x.shape, dtype=jax.dtypes.float0)


def _bwd_masked(causal, scale, q_tile, block_k, interpret, xla_backward,
                window, res, g):
    q, k, v, kv_mask, window_on, out, lse = res
    if xla_backward:
        masked_by = _xla_window(window, window_on, k.shape[2])
        _, vjp = jax.vjp(
            lambda q, k, v: _xla_attention(q, k, v, kv_mask, causal,
                                           scale, masked_by), q, k, v)
        dq, dk, dv = vjp(g)
    else:
        dq, dk, dv = _flash_backward(q, k, v, kv_mask, out, lse, g,
                                     causal, scale, q_tile, block_k,
                                     interpret, window=window,
                                     window_on=window_on)
    return dq, dk, dv, _no_cotangent(kv_mask), _no_cotangent(window_on)


_flash_attention_masked.defvjp(_fwd_masked, _bwd_masked)


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(5, 6, 7, 8, 9, 10, 11))
def _flash_attention_with_lse(q, k, v, kv_mask, window_on, causal, scale,
                              q_tile, block_k, interpret, xla_backward,
                              window):
    """(out, lse) variant — the composition surface for ring attention:
    per-block partial softmaxes merge exactly from (out, lse) pairs, and
    the lse cotangent is a delta-shift in the unchanged backward kernel."""
    return _flash_forward(q, k, v, kv_mask, causal, scale, q_tile,
                          block_k, interpret, window, window_on)


def _fwd_lse(q, k, v, kv_mask, window_on, causal, scale, q_tile, block_k,
             interpret, xla_backward, window):
    out, lse = _flash_forward(q, k, v, kv_mask, causal, scale, q_tile,
                              block_k, interpret, window, window_on)
    return (out, lse), (q, k, v, kv_mask, window_on, out, lse)


def _xla_attention_lse(q, k, v, kv_mask, causal, scale, window=None):
    k, v = _repeat_kv(q, k, v)
    s = _xla_scores(q, k, kv_mask, causal, scale, window)
    lse = jax.nn.logsumexp(s, axis=-1)
    # clamp so fully-masked rows (lse == -inf) yield 0, not exp(nan)
    p = jnp.exp(s - jnp.maximum(lse, _NEG_INF)[..., None])
    p = jnp.where(s > _NEG_INF / 2, p, 0.0)
    out = jnp.einsum("bhqk,bhkd->bhqd", p,
                     v.astype(jnp.float32)).astype(q.dtype)
    return out, lse


def _bwd_lse(causal, scale, q_tile, block_k, interpret, xla_backward, window,
             res, g):
    q, k, v, kv_mask, window_on, out, lse = res
    dout, dlse = g
    if xla_backward:
        masked_by = _xla_window(window, window_on, k.shape[2])
        _, vjp = jax.vjp(
            lambda q, k, v: _xla_attention_lse(q, k, v, kv_mask, causal,
                                               scale, masked_by), q, k, v)
        dq, dk, dv = vjp((dout, dlse))
    else:
        dq, dk, dv = _flash_backward(q, k, v, kv_mask, out, lse, dout,
                                     causal, scale, q_tile, block_k,
                                     interpret, dlse=dlse, window=window,
                                     window_on=window_on)
    return dq, dk, dv, _no_cotangent(kv_mask), _no_cotangent(window_on)


_flash_attention_with_lse.defvjp(_fwd_lse, _bwd_lse)


def _call_arguments(q, causal, scale, kv_mask, interpret, window,
                    window_on):
    """The defaults and checks the two public entries share: ``(scale,
    interpret, kv_mask, window, window_on)``."""
    if scale is None:
        scale = 1.0 / np.sqrt(q.shape[-1])
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if kv_mask is not None:
        kv_mask = kv_mask.astype(jnp.int32)
    if window is None:
        if window_on is not None:
            raise ValueError("window_on goes with a window")
    else:
        if not causal or int(window) < 1:
            raise ValueError("a window is a positive count of keys behind "
                             "a causal diagonal")
        window = int(window)
        if window_on is not None:
            window_on = jnp.asarray(window_on, bool)
    return float(scale), interpret, kv_mask, window, window_on


def flash_attention_lse(q: jax.Array, k: jax.Array, v: jax.Array,
                        causal: bool = False,
                        scale: Optional[float] = None,
                        kv_mask: Optional[jax.Array] = None,
                        q_tile: int = 256, block_k: int = 256,
                        interpret: Optional[bool] = None,
                        xla_backward: bool = False,
                        window: Optional[int] = None,
                        window_on: Optional[jax.Array] = None):
    """Fused attention returning (out [B, T, H, D], lse [B, H, T]).

    Same kernels as `flash_attention` plus the log-sum-exp output, so a
    caller (ops/ring_attention.py block_impl='pallas') can merge partial
    attentions over key blocks exactly: out = Σ_b out_b·exp(lse_b-lse),
    lse = logaddexp_b(lse_b). Differentiable in all inputs including
    through lse. ``window`` and ``window_on`` as `flash_attention`'s.
    """
    scale, interpret, kv_mask, window, window_on = _call_arguments(
        q, causal, scale, kv_mask, interpret, window, window_on)
    out, lse = _flash_attention_with_lse(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), kv_mask, window_on, causal, scale, q_tile,
        block_k, interpret, xla_backward, window)
    return out.transpose(0, 2, 1, 3), lse


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = False,
                    scale: Optional[float] = None,
                    kv_mask: Optional[jax.Array] = None,
                    q_tile: int = 256, block_k: int = 256,
                    interpret: Optional[bool] = None,
                    xla_backward: bool = False,
                    window: Optional[int] = None,
                    window_on: Optional[jax.Array] = None) -> jax.Array:
    """Fused attention: q [B, T, Hq, D], k, v [B, Tk, Hkv, D] -> [B, T,
    Hq, D]; ``Hq`` a multiple of ``Hkv`` (query head ``h`` reads
    key/value head ``h // (Hq / Hkv)``).

    ``kv_mask`` [B, Tk] marks attendable key positions (padding mask for
    NMT/BERT-style models); None means all keys attend. ``interpret``
    defaults to True off-TPU (so CPU tests exercise the same kernels)
    and False on TPU. ``xla_backward=True`` swaps the Pallas backward
    kernel for the einsum-recompute fallback.

    ``window`` (static, with ``causal=True``): query ``t`` reads key
    ``s`` iff ``0 <= t - s < window``, the token itself and the ``window
    - 1`` before it; any positive count is legal, ``>= Tk`` being plain
    causal attention. The two kernels then walk the band's tiles only
    and are named ``flash_fwd_win`` and ``flash_bwd_win`` under the
    scope ``window_attention``, on the call's own tiles (at
    32 on 4 heads of 128, 8,192 keys and a window of 1,024 the v5e runs
    512 x 512 fastest of nine pairs: ``PERF.md`` section 6, PR 33).
    At 20 on 20 heads of 256 over 8,192 causal keys (latent
    attention's) 512 x 512 takes 5.52 ms forward and 21.82 forward and
    backward on a v5e, and beat 256 x 512 (5.47 and 22.68); 1024 x 1024
    read 5.60 and 21.68, within 1 % either way (nine pairs,
    ``PERF.md`` section 6). Both head sizes compile under the same
    VMEM limit.
    ``window_on``, a traced boolean scalar, says whether THIS call applies the window (absent:
    always): one loop body then serves layers of both kinds, a ``cond``
    choosing between the windowed calls and the plain ones. Without a
    ``window`` nothing of this is traced.
    """
    scale, interpret, kv_mask, window, window_on = _call_arguments(
        q, causal, scale, kv_mask, interpret, window, window_on)
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    out = _flash_attention_masked(qt, kt, vt, kv_mask, window_on, causal,
                                  scale, q_tile, block_k, interpret,
                                  xla_backward, window)
    return out.transpose(0, 2, 1, 3)
