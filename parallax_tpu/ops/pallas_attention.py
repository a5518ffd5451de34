"""Pallas flash-attention forward kernel (TPU).

The hot op of every transformer family here (NMT, BERT, long-context,
MoE-LM) is attention; this is its Pallas implementation: one fused kernel
per (batch, head, q-tile) program that streams K/V tiles through VMEM
with the online-softmax recurrence — the [Tq, Tk] score matrix never
exists in HBM.

Gradients: fully-Pallas backward — the forward kernel additionally emits
the per-row logsumexp; the backward recomputes P tiles from (q, k, lse)
and accumulates dq (one kernel, grid over q-tiles) and dk/dv (one
kernel, grid over k-tiles) flash-attention style, so the backward never
materializes [Tq, Tk] either. Set ``xla_backward=True`` to fall back to
the einsum-recompute backward.

Grouped key/value heads: ``q`` may bring ``g`` times the heads of ``k``
and ``v`` (query head ``h`` reads key/value head ``h // g``). No kernel
repeats K or V in HBM: the forward and ``dq`` kernels map ``g``
consecutive query heads onto one key/value block (fetched once for the
group, the grid walking the heads in order), and the ``dk``/``dv``
kernel walks a group's query heads in its last grid axis and sums their
contributions in a float32 scratch. The three calls are named
``flash_fwd``, ``flash_dq`` and ``flash_dkv``.

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


def _flash_fwd_kernel(*refs, kv_len: int, block_k: int, causal: bool,
                      scale: float, q_tile: int, has_mask: bool):
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

    def body(kt, carry):
        m, l, acc = carry
        k_blk = k_ref[0, 0, pl.dslice(kt * block_k, block_k), :]
        v_blk = v_ref[0, 0, pl.dslice(kt * block_k, block_k), :]
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)             # [q_tile, bk]
        if mask_ref is not None:
            kv_ok = mask_ref[0, 0, pl.dslice(kt * block_k, block_k)]
            s = jnp.where(kv_ok[None, :] > 0, s, _NEG_INF)
        if causal:
            q_pos = qt * q_tile + jax.lax.broadcasted_iota(
                jnp.int32, (q_tile, block_k), 0)
            k_pos = kt * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (q_tile, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        alpha = jnp.exp(jnp.minimum(m - m_new, 0.0))
        p = jnp.exp(s - m_new[:, None])
        p = jnp.where(s > _NEG_INF / 2, p, 0.0)
        l = l * alpha + p.sum(axis=-1)
        acc = acc * alpha[:, None] + jax.lax.dot_general(
            p, v_blk.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    m, l, acc = jax.lax.fori_loop(0, num_k, body, (m, l, acc))
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


def _params(*semantics):
    from jax.experimental.pallas import tpu as pltpu
    # a whole key/value head (forward, dq) or a whole query head (dkv)
    # stays in VMEM beside its second buffer: 8 MB at 8,192 x 128 bf16
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=64 * 1024 * 1024)


def _flash_forward(q, k, v, kv_mask, causal: bool, scale: float,
                   q_tile: int, block_k: int, interpret: bool):
    """q: [B, Hq, T, D]; k, v: [B, Hkv, Tk, D]; kv_mask: [B, Tk] int32
    (1 = attendable). Returns (out [B, Hq, T, D], lse [B, Hq, T])."""
    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    g = _group(q, k)
    q_tile = _snap(q_tile, Tq)
    block_k = _snap(block_k, Tk)
    grid = (B, H, Tq // q_tile)
    has_mask = kv_mask is not None
    kernel = functools.partial(
        _flash_fwd_kernel, kv_len=Tk, block_k=block_k, causal=causal,
        scale=scale, q_tile=q_tile, has_mask=has_mask)
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
        name="flash_fwd", interpret=interpret,
    )(*operands)
    return out, lse[..., 0]


def _flash_dq_kernel(*refs, kv_len: int, block_k: int, causal: bool,
                     scale: float, q_tile: int, has_mask: bool):
    if has_mask:
        (q_ref, k_ref, v_ref, mask_ref, do_ref, lse_ref, delta_ref,
         dq_ref) = refs
    else:
        q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref = refs
        mask_ref = None
    qt = pl.program_id(2)
    q = q_ref[0, 0] * scale                                # [qt, D]
    do = do_ref[0, 0].astype(jnp.float32)                  # [qt, D]
    lse = lse_ref[0, 0][:, 0]                              # [qt] (lane 0)
    delta = delta_ref[0, 0][:, 0]                          # [qt]
    D = q.shape[-1]
    dq = jnp.zeros((q_tile, D), jnp.float32)
    num_k = kv_len // block_k
    if causal:
        num_k = jnp.minimum(
            num_k, ((qt + 1) * q_tile + block_k - 1) // block_k)

    def body(kt, dq):
        k_blk = k_ref[0, 0, pl.dslice(kt * block_k, block_k), :]
        v_blk = v_ref[0, 0, pl.dslice(kt * block_k, block_k), :]
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)            # [qt, bk]
        if mask_ref is not None:
            kv_ok = mask_ref[0, 0, pl.dslice(kt * block_k, block_k)]
            s = jnp.where(kv_ok[None, :] > 0, s, _NEG_INF)
        if causal:
            q_pos = qt * q_tile + jax.lax.broadcasted_iota(
                jnp.int32, (q_tile, block_k), 0)
            k_pos = kt * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (q_tile, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        p = jnp.exp(s - lse[:, None])
        p = jnp.where(s > _NEG_INF / 2, p, 0.0)
        dp = jax.lax.dot_general(
            do, v_blk.astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)            # [qt, bk]
        ds = p * (dp - delta[:, None])
        return dq + jax.lax.dot_general(
            ds, k_blk.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
    dq = jax.lax.fori_loop(0, num_k, body, dq)
    dq_ref[0, 0] = (dq * scale).astype(dq_ref.dtype)


def _flash_dkv_kernel(*refs, q_len: int, q_blk: int, causal: bool,
                      scale: float, k_tile: int, has_mask: bool,
                      group: int):
    """``dk`` and ``dv`` of one key tile of one key/value head. The last
    grid axis walks the ``group`` query heads that read this head; their
    contributions add up in the float32 scratch and leave with the last.
    The tile's scores are held transposed, ``[keys, queries]``, so that
    the per-query ``lse`` and ``delta`` broadcast along sublanes from
    lane-major rows and all four products are plain or NT."""
    if has_mask:
        (q_ref, k_ref, v_ref, mask_ref, do_ref, lse_ref, delta_ref,
         dk_ref, dv_ref, dk_acc, dv_acc) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref,
         dv_ref, dk_acc, dv_acc) = refs
        mask_ref = None
    kt = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    k = k_ref[0, 0]                                        # [kt_, D]
    v = v_ref[0, 0].astype(jnp.float32)
    num_q = q_len // q_blk
    # Q blocks entirely before this k-tile's diagonal see none of it
    q_lo = (kt * k_tile) // q_blk if causal else 0

    def body(qi, carry):
        dk, dv = carry
        q = q_ref[0, 0, pl.dslice(qi * q_blk, q_blk), :] * scale
        do = do_ref[0, 0, pl.dslice(qi * q_blk, q_blk), :].astype(
            jnp.float32)
        lse = lse_ref[0, 0, qi]                            # [1, qb]
        delta = delta_ref[0, 0, qi]
        st = jax.lax.dot_general(
            k, q, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)            # [kt_, qb]
        if mask_ref is not None:
            st = jnp.where(mask_ref[0][:, 0:1] > 0, st, _NEG_INF)
        if causal:
            k_pos = kt * k_tile + jax.lax.broadcasted_iota(
                jnp.int32, (k_tile, q_blk), 0)
            q_pos = qi * q_blk + jax.lax.broadcasted_iota(
                jnp.int32, (k_tile, q_blk), 1)
            st = jnp.where(q_pos >= k_pos, st, _NEG_INF)
        pt = jnp.exp(st - lse)
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
        return dk, dv
    dk, dv = jax.lax.fori_loop(q_lo, num_q, body, (dk_acc[...], dv_acc[...]))
    dk_acc[...] = dk
    dv_acc[...] = dv

    @pl.when(j == group - 1)
    def _():
        # q was pre-scaled, so dk absorbed one factor of `scale` already
        dk_ref[0, 0] = dk.astype(dk_ref.dtype)
        dv_ref[0, 0] = dv.astype(dv_ref.dtype)


def _flash_backward(q, k, v, kv_mask, out, lse, g, causal, scale,
                    q_tile, block_k, interpret, dlse=None):
    from jax.experimental.pallas import tpu as pltpu
    B, H, Tq, D = q.shape
    Hkv, Tk = k.shape[1], k.shape[2]
    grp = _group(q, k)
    q_tile = _snap(q_tile, Tq)
    block_k = _snap(block_k, Tk)
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)                               # [B, H, Tq]
    if dlse is not None:
        # lse cotangent folds into the existing kernels exactly:
        # d s = p*(dp - delta) + dlse*p = p*(dp - (delta - dlse))
        delta = delta - dlse.astype(jnp.float32)

    has_mask = kv_mask is not None
    dq_specs = [
        pl.BlockSpec((1, 1, q_tile, D), lambda b, h, i: (b, h, i, 0)),
        pl.BlockSpec((1, 1, Tk, D), lambda b, h, i: (b, h // grp, 0, 0)),
        pl.BlockSpec((1, 1, Tk, D), lambda b, h, i: (b, h // grp, 0, 0)),
    ]
    dq_operands = [q, k, v]
    if has_mask:
        dq_specs.append(pl.BlockSpec((1, 1, Tk),
                                     lambda b, h, i: (b, 0, 0)))
        dq_operands.append(kv_mask[:, None, :])
    # lse/delta travel lane-broadcast (see _LANES comment)
    lse_b = jnp.broadcast_to(lse[..., None], (*lse.shape, _LANES))
    delta_b = jnp.broadcast_to(delta[..., None], (*delta.shape, _LANES))
    dq_specs += [
        pl.BlockSpec((1, 1, q_tile, D), lambda b, h, i: (b, h, i, 0)),
        pl.BlockSpec((1, 1, q_tile, _LANES), lambda b, h, i: (b, h, i, 0)),
        pl.BlockSpec((1, 1, q_tile, _LANES), lambda b, h, i: (b, h, i, 0)),
    ]
    dq_operands += [g, lse_b, delta_b]
    dq = pl.pallas_call(
        functools.partial(_flash_dq_kernel, kv_len=Tk, block_k=block_k,
                          causal=causal, scale=scale, q_tile=q_tile,
                          has_mask=has_mask),
        grid=(B, H, Tq // q_tile),
        in_specs=dq_specs,
        out_specs=pl.BlockSpec((1, 1, q_tile, D),
                               lambda b, h, i: (b, h, i, 0)),
        out_shape=_sds((B, H, Tq, D), q.dtype, q),
        compiler_params=_params("parallel", "parallel", "parallel"),
        name="flash_dq", interpret=interpret,
    )(*dq_operands)

    # grid (batch, key/value head, key tile, query head of the group):
    # a key tile's dk and dv leave once, after the group's last head
    def of_q(b, hk, j, x):
        return (b, hk * grp + x, 0, 0)

    def of_row(b, hk, j, x):
        return (b, hk * grp + x, 0, 0, 0)

    def of_kv(b, hk, j, x):
        return (b, hk, j, 0)

    dkv_specs = [
        pl.BlockSpec((1, 1, Tq, D), of_q),
        pl.BlockSpec((1, 1, block_k, D), of_kv),
        pl.BlockSpec((1, 1, block_k, D), of_kv),
    ]
    dkv_operands = [q, k, v]
    if has_mask:
        # down the sublanes here: the scores are held [keys, queries]
        dkv_specs.append(pl.BlockSpec((1, block_k, _LANES),
                                      lambda b, hk, j, x: (b, j, 0)))
        dkv_operands.append(jnp.broadcast_to(
            kv_mask[:, :, None], (*kv_mask.shape, _LANES)))
    # ... and lse/delta along the lanes, one row a query block
    nq = Tq // q_tile
    rows = pl.BlockSpec((1, 1, nq, 1, q_tile), of_row)
    dkv_specs += [pl.BlockSpec((1, 1, Tq, D), of_q), rows, rows]
    dkv_operands += [g, lse.reshape(B, H, nq, 1, q_tile),
                     delta.reshape(B, H, nq, 1, q_tile)]
    dk, dv = pl.pallas_call(
        functools.partial(_flash_dkv_kernel, q_len=Tq, q_blk=q_tile,
                          causal=causal, scale=scale, k_tile=block_k,
                          has_mask=has_mask, group=grp),
        grid=(B, Hkv, Tk // block_k, grp),
        in_specs=dkv_specs,
        out_specs=[pl.BlockSpec((1, 1, block_k, D), of_kv),
                   pl.BlockSpec((1, 1, block_k, D), of_kv)],
        out_shape=[
            _sds((B, Hkv, Tk, D), k.dtype, k),
            _sds((B, Hkv, Tk, D), v.dtype, v),
        ],
        scratch_shapes=[pltpu.VMEM((block_k, D), jnp.float32),
                        pltpu.VMEM((block_k, D), jnp.float32)],
        compiler_params=_params("parallel", "parallel", "parallel",
                                "arbitrary"),
        name="flash_dkv", interpret=interpret,
    )(*dkv_operands)
    return dq, dk, dv


def _repeat_kv(q, k, v):
    g = _group(q, k)
    if g == 1:
        return k, v
    return jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)


def _xla_attention(q, k, v, kv_mask, causal, scale):
    k, v = _repeat_kv(q, k, v)
    s = jnp.einsum("bhqd,bhkd->bhqk", q * scale, k,
                   preferred_element_type=jnp.float32)
    if kv_mask is not None:
        s = jnp.where(kv_mask[:, None, None, :] > 0, s, _NEG_INF)
    if causal:
        T, Tk = q.shape[2], k.shape[2]
        mask = jnp.tril(jnp.ones((T, Tk), bool))
        s = jnp.where(mask[None, None], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    # fully-masked rows: zero the uniform softmax so outputs and grads
    # match the Pallas kernels (which emit exact zeros there)
    p = jnp.where(s > _NEG_INF / 2, p, 0.0)
    return jnp.einsum("bhqk,bhkd->bhqd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _flash_attention_masked(q, k, v, kv_mask, causal, scale, q_tile,
                            block_k, interpret, xla_backward):
    out, _ = _flash_forward(q, k, v, kv_mask, causal, scale, q_tile,
                            block_k, interpret)
    return out


def _fwd_masked(q, k, v, kv_mask, causal, scale, q_tile, block_k,
                interpret, xla_backward):
    out, lse = _flash_forward(q, k, v, kv_mask, causal, scale, q_tile,
                              block_k, interpret)
    # what a caller's checkpoint policy may keep for the backward pass
    # in place of running the forward kernel again
    out = checkpoint_name(out, "flash_attn")
    lse = checkpoint_name(lse, "flash_attn")
    return out, (q, k, v, kv_mask, out, lse)


def _bwd_masked(causal, scale, q_tile, block_k, interpret, xla_backward,
                res, g):
    q, k, v, kv_mask, out, lse = res
    if xla_backward:
        _, vjp = jax.vjp(
            lambda q, k, v: _xla_attention(q, k, v, kv_mask, causal,
                                           scale), q, k, v)
        dq, dk, dv = vjp(g)
    else:
        dq, dk, dv = _flash_backward(q, k, v, kv_mask, out, lse, g,
                                     causal, scale, q_tile, block_k,
                                     interpret)
    mask_ct = (None if kv_mask is None else
               np.zeros(kv_mask.shape, dtype=jax.dtypes.float0))
    return dq, dk, dv, mask_ct


_flash_attention_masked.defvjp(_fwd_masked, _bwd_masked)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _flash_attention_with_lse(q, k, v, kv_mask, causal, scale, q_tile,
                              block_k, interpret, xla_backward):
    """(out, lse) variant — the composition surface for ring attention:
    per-block partial softmaxes merge exactly from (out, lse) pairs, and
    the lse cotangent is a delta-shift in the unchanged backward kernels."""
    return _flash_forward(q, k, v, kv_mask, causal, scale, q_tile,
                          block_k, interpret)


def _fwd_lse(q, k, v, kv_mask, causal, scale, q_tile, block_k,
             interpret, xla_backward):
    out, lse = _flash_forward(q, k, v, kv_mask, causal, scale, q_tile,
                              block_k, interpret)
    return (out, lse), (q, k, v, kv_mask, out, lse)


def _xla_attention_lse(q, k, v, kv_mask, causal, scale):
    k, v = _repeat_kv(q, k, v)
    s = jnp.einsum("bhqd,bhkd->bhqk", q * scale, k,
                   preferred_element_type=jnp.float32)
    if kv_mask is not None:
        s = jnp.where(kv_mask[:, None, None, :] > 0, s, _NEG_INF)
    if causal:
        T, Tk = q.shape[2], k.shape[2]
        mask = jnp.tril(jnp.ones((T, Tk), bool))
        s = jnp.where(mask[None, None], s, _NEG_INF)
    lse = jax.nn.logsumexp(s, axis=-1)
    # clamp so fully-masked rows (lse == -inf) yield 0, not exp(nan)
    p = jnp.exp(s - jnp.maximum(lse, _NEG_INF)[..., None])
    p = jnp.where(s > _NEG_INF / 2, p, 0.0)
    out = jnp.einsum("bhqk,bhkd->bhqd", p,
                     v.astype(jnp.float32)).astype(q.dtype)
    return out, lse


def _bwd_lse(causal, scale, q_tile, block_k, interpret, xla_backward,
             res, g):
    q, k, v, kv_mask, out, lse = res
    dout, dlse = g
    if xla_backward:
        _, vjp = jax.vjp(
            lambda q, k, v: _xla_attention_lse(q, k, v, kv_mask, causal,
                                               scale), q, k, v)
        dq, dk, dv = vjp((dout, dlse))
    else:
        dq, dk, dv = _flash_backward(q, k, v, kv_mask, out, lse, dout,
                                     causal, scale, q_tile, block_k,
                                     interpret, dlse=dlse)
    mask_ct = (None if kv_mask is None else
               np.zeros(kv_mask.shape, dtype=jax.dtypes.float0))
    return dq, dk, dv, mask_ct


_flash_attention_with_lse.defvjp(_fwd_lse, _bwd_lse)


def flash_attention_lse(q: jax.Array, k: jax.Array, v: jax.Array,
                        causal: bool = False,
                        scale: Optional[float] = None,
                        kv_mask: Optional[jax.Array] = None,
                        q_tile: int = 256, block_k: int = 256,
                        interpret: Optional[bool] = None,
                        xla_backward: bool = False):
    """Fused attention returning (out [B, T, H, D], lse [B, H, T]).

    Same kernels as `flash_attention` plus the log-sum-exp output, so a
    caller (ops/ring_attention.py block_impl='pallas') can merge partial
    attentions over key blocks exactly: out = Σ_b out_b·exp(lse_b-lse),
    lse = logaddexp_b(lse_b). Differentiable in all inputs including
    through lse.
    """
    if scale is None:
        scale = 1.0 / np.sqrt(q.shape[-1])
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if kv_mask is not None:
        kv_mask = kv_mask.astype(jnp.int32)
    out, lse = _flash_attention_with_lse(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), kv_mask, causal, float(scale), q_tile,
        block_k, interpret, xla_backward)
    return out.transpose(0, 2, 1, 3), lse


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = False,
                    scale: Optional[float] = None,
                    kv_mask: Optional[jax.Array] = None,
                    q_tile: int = 256, block_k: int = 256,
                    interpret: Optional[bool] = None,
                    xla_backward: bool = False) -> jax.Array:
    """Fused attention: q [B, T, Hq, D], k, v [B, Tk, Hkv, D] -> [B, T,
    Hq, D]; ``Hq`` a multiple of ``Hkv`` (query head ``h`` reads
    key/value head ``h // (Hq / Hkv)``).

    ``kv_mask`` [B, Tk] marks attendable key positions (padding mask for
    NMT/BERT-style models); None means all keys attend. ``interpret``
    defaults to True off-TPU (so CPU tests exercise the same kernels)
    and False on TPU. ``xla_backward=True`` swaps the Pallas backward
    kernels for the einsum-recompute fallback.
    """
    if scale is None:
        scale = 1.0 / np.sqrt(q.shape[-1])
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if kv_mask is not None:
        kv_mask = kv_mask.astype(jnp.int32)
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    out = _flash_attention_masked(qt, kt, vt, kv_mask, causal,
                                  float(scale), q_tile, block_k,
                                  interpret, xla_backward)
    return out.transpose(0, 2, 1, 3)
