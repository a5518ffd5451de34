"""Ring attention — sequence/context parallelism over an ICI ring.

The reference has no sequence parallelism (SURVEY.md §5.7: LSTM-era
models, sequence length is a plain hyperparameter). For the TPU rebuild
long-context is first-class: attention over sequences sharded across a
mesh axis, with K/V blocks rotated around the ring via `ppermute` while
each device accumulates its queries' attention online (flash-attention
style running max/denominator), so no device ever materializes the full
sequence or the full [T, T] score matrix.

Per ring step each device holds one K/V block and overlaps compute with
the neighbor exchange; communication per device per step is the K/V block
(2 · B · T/n · H · D), independent of the number of devices — the
all-to-all sequence-parallel cost model.

Differentiable: the ring loop is a `lax.scan` (static trip count =
ring size), so reverse-mode AD threads the same ring backwards.

Causal placements:
  * ``placement='contiguous'`` (default): device i holds rows
    [i·T/n, (i+1)·T/n). Simple layout, but causal masking discards
    ~half the score FLOPs and device 0 does the least useful work.
  * ``placement='zigzag'``: device i holds the low block i and the
    mirrored high block 2n-1-i (each T/2n rows), so every device
    carries the same causal workload. Inputs must be pre-permuted with
    `zigzag_permutation` (outputs come back in the same zigzag layout;
    invert with `inverse_zigzag_permutation`). Engine-level automatic
    resharding is roadmap item 2.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

_NEG_INF = -1e30


def zigzag_permutation(T: int, n: int) -> np.ndarray:
    """perm such that zigzag_layout = real[..., perm, ...]: device i's
    shard is real blocks (i, 2n-1-i), each of T/(2n) rows."""
    if T % (2 * n):
        raise ValueError(
            f"zigzag placement needs sequence length divisible by "
            f"2*ring={2 * n}; got T={T}")
    h = T // (2 * n)
    idx = []
    for i in range(n):
        idx.extend(range(i * h, (i + 1) * h))
        idx.extend(range((2 * n - 1 - i) * h, (2 * n - i) * h))
    return np.asarray(idx)


def inverse_zigzag_permutation(T: int, n: int) -> np.ndarray:
    perm = zigzag_permutation(T, n)
    inv = np.empty_like(perm)
    inv[perm] = np.arange(T)
    return inv


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                   mesh: Mesh, axis: str,
                   causal: bool = False,
                   scale: Optional[float] = None,
                   batch_axis: Optional[str] = None,
                   placement: str = "contiguous",
                   block_impl: str = "auto") -> jax.Array:
    """Attention with the sequence dimension sharded over ``axis``.

    q, k, v: [B, T, H, D] with T sharded over ``axis`` (global views);
    ``batch_axis`` optionally shards B over another mesh axis (dp x sp).
    Returns [B, T, H, D] sharded the same way.

    ``block_impl`` selects the per-block attention core: 'xla' (einsum
    online-softmax), 'pallas' (the flash kernels of
    ops/pallas_attention — each block tile runs fused in VMEM and the
    partials merge exactly from the kernels' (out, lse); ~flash-level
    HBM traffic inside the ring), or 'auto' (pallas on TPU backends,
    xla elsewhere).
    """
    if scale is None:
        scale = 1.0 / np.sqrt(q.shape[-1])
    if placement not in ("contiguous", "zigzag"):
        raise ValueError(f"unknown placement {placement!r}")
    if block_impl not in ("auto", "xla", "pallas"):
        raise ValueError(f"unknown block_impl {block_impl!r}")
    use_flash = (block_impl == "pallas"
                 or (block_impl == "auto"
                     and jax.default_backend() == "tpu"))
    # Pallas INTERPRET mode (CPU tests) trips the shard_map VMA checker
    # (jax suggests check_vma=False as the workaround); compiled TPU
    # kernels carry their vma (ops/pallas_attention._sds) and keep the
    # checker on.
    flash_interpret = use_flash and jax.default_backend() != "tpu"
    zigzag = placement == "zigzag"
    n = mesh.shape[axis]
    if zigzag and q.shape[1] % (2 * n):
        raise ValueError(
            f"zigzag placement needs T divisible by 2*n ({2 * n})")
    spec = P(batch_axis, axis, None, None)

    def local(q_loc, k_loc, v_loc):
        # q_loc: [B, Tq, H, D] — this device's query block.
        idx = jax.lax.axis_index(axis)
        B, Tq, H, D = q_loc.shape

        def positions(origin):
            """Real sequence positions of the block originating on
            device ``origin`` (traced scalar), length Tq."""
            if not zigzag:
                return origin * Tq + jnp.arange(Tq)
            h = Tq // 2
            lo = origin * h + jnp.arange(h)
            hi = (2 * n - 1 - origin) * h + jnp.arange(h)
            return jnp.concatenate([lo, hi])

        qh = (q_loc * scale).transpose(0, 2, 1, 3)        # [B, H, Tq, D]

        # mark the accumulators as device-varying over every mesh axis the
        # blocks vary over, so the scan carry type matches its output
        # (they pick up per-device values). No pcast when the checker is
        # off (flash interpret mode) — it must not be emitted there.
        vary = (axis,) if batch_axis is None else (axis, batch_axis)

        def pvary(x):
            if flash_interpret:
                return x
            return jax.lax.pcast(x, vary, to="varying")

        m0 = pvary(jnp.full((B, H, Tq), _NEG_INF, jnp.float32))
        l0 = pvary(jnp.zeros((B, H, Tq), jnp.float32))
        o0 = pvary(jnp.zeros((B, H, Tq, D), jnp.float32))

        def online_update(scores, vh, m, l, o):
            """Flash-style online softmax update of (m, l, o) with a new
            score tile (callers pre-mask or pass maskless tiles)."""
            m_new = jnp.maximum(m, scores.max(axis=-1))
            alpha = jnp.exp(jnp.minimum(m - m_new, 0.0))
            p = jnp.exp(scores - m_new[..., None])
            # fully-masked rows have scores == m_new == _NEG_INF, where
            # exp(0) would leak mass — zero them explicitly
            p = jnp.where(scores > _NEG_INF / 2, p, 0.0)
            l = l * alpha + p.sum(axis=-1)
            o = (o * alpha[..., None]
                 + jnp.einsum("bhqk,bhkd->bhqd", p,
                              vh.astype(jnp.float32)))
            return m_new, l, o

        def accumulate(k_blk, v_blk, s, m, l, o):
            # Block s originated on device (idx - s) mod n.
            kv_origin = (idx - s) % n
            kh = k_blk.transpose(0, 2, 1, 3)              # [B, H, Tk, D]
            vh = v_blk.transpose(0, 2, 1, 3)
            scores = jnp.einsum(
                "bhqd,bhkd->bhqk", qh, kh,
                preferred_element_type=jnp.float32)       # [B,H,Tq,Tk]
            if causal:
                q_pos = positions(idx)
                k_pos = positions(kv_origin)
                mask = q_pos[:, None] >= k_pos[None, :]
                scores = jnp.where(mask[None, None], scores, _NEG_INF)
            return online_update(scores, vh, m, l, o)

        def normalize(l, o):
            denom = jnp.maximum(l, 1e-30)[..., None]
            out = (o / denom).transpose(0, 2, 1, 3)       # [B, Tq, H, D]
            return out.astype(q_loc.dtype)

        rot_perm = [(i, (i + 1) % n) for i in range(n)]

        def rotate(k_blk, v_blk):
            return (jax.lax.ppermute(k_blk, axis, rot_perm),
                    jax.lax.ppermute(v_blk, axis, rot_perm))

        if use_flash:
            # Per-block attention runs the fused flash kernels
            # (ops/pallas_attention); partials fold into the online
            # (m, l, o) accumulators exactly via each tile's lse.
            from parallax_tpu.ops.pallas_attention import (
                flash_attention_lse)

            def flash_merge(q_sub, k_sub, v_sub, flash_causal, m, l, o):
                """One flash tile (q_sub [B, Tq', H, D] x k/v_sub
                [B, Tk', H, D]) merged into row-aligned (m, l, o)."""
                out_b, lse_b = flash_attention_lse(
                    q_sub, k_sub, v_sub, causal=flash_causal,
                    scale=scale)
                ob = out_b.transpose(0, 2, 1, 3).astype(jnp.float32)
                m_new = jnp.maximum(m, lse_b)
                alpha = jnp.exp(jnp.minimum(m - m_new, 0.0))
                w = jnp.exp(lse_b - m_new)
                l = l * alpha + w
                o = o * alpha[..., None] + ob * w[..., None]
                return m_new, l, o

            if causal and zigzag and n > 1:
                # self tile: three maskful/maskless quadrants (lo-lo
                # causal, hi-lo full, hi-hi causal; lo-hi is masked)
                h = Tq // 2
                q_lo, q_hi = q_loc[:, :h], q_loc[:, h:]
                m_lo, l_lo, o_lo = flash_merge(
                    q_lo, k_loc[:, :h], v_loc[:, :h], True,
                    m0[:, :, :h], l0[:, :, :h], o0[:, :, :h])
                m_hi, l_hi, o_hi = flash_merge(
                    q_hi, k_loc[:, :h], v_loc[:, :h], False,
                    m0[:, :, h:], l0[:, :, h:], o0[:, :, h:])
                m_hi, l_hi, o_hi = flash_merge(
                    q_hi, k_loc[:, h:], v_loc[:, h:], True,
                    m_hi, l_hi, o_hi)
                m = jnp.concatenate([m_lo, m_hi], 2)
                l = jnp.concatenate([l_lo, l_hi], 2)
                o = jnp.concatenate([o_lo, o_hi], 2)

                def fstep(carry, s):
                    k_blk, v_blk, m, l, o = carry
                    k_blk, v_blk = rotate(k_blk, v_blk)
                    kv_origin = (idx - s) % n

                    def earlier(args):
                        k_blk, v_blk, m, l, o = args
                        return flash_merge(q_loc, k_blk[:, :h],
                                           v_blk[:, :h], False, m, l, o)

                    def later(args):
                        k_blk, v_blk, m, l, o = args
                        m_hi, l_hi, o_hi = flash_merge(
                            q_loc[:, h:], k_blk, v_blk, False,
                            m[:, :, h:], l[:, :, h:], o[:, :, h:])
                        return (jnp.concatenate([m[:, :, :h], m_hi], 2),
                                jnp.concatenate([l[:, :, :h], l_hi], 2),
                                jnp.concatenate([o[:, :, :h], o_hi], 2))

                    m, l, o = jax.lax.cond(kv_origin < idx, earlier,
                                           later,
                                           (k_blk, v_blk, m, l, o))
                    return (k_blk, v_blk, m, l, o), None

                (_, _, m, l, o), _ = jax.lax.scan(
                    fstep, (k_loc, v_loc, m, l, o), jnp.arange(1, n))
                return normalize(l, o)

            def consume(k_blk, v_blk, s, m, l, o):
                """One contiguous-placement block through the flash
                core: self block in-block causal, earlier blocks full,
                later blocks fully masked -> skip (the flash analogue
                of `accumulate`). Shared by the scan body and the final
                un-rotated block."""
                kv_origin = (idx - s) % n

                def self_tile(args):
                    return flash_merge(q_loc, args[0], args[1], True,
                                       *args[2:])

                def full_tile(args):
                    return flash_merge(q_loc, args[0], args[1], False,
                                       *args[2:])

                if not causal:
                    return full_tile((k_blk, v_blk, m, l, o))
                return jax.lax.cond(
                    kv_origin <= idx,
                    lambda a: jax.lax.cond(kv_origin == idx,
                                           self_tile, full_tile, a),
                    lambda a: (a[2], a[3], a[4]),
                    (k_blk, v_blk, m, l, o))

            def fstep(carry, s):
                k_blk, v_blk, m, l, o = carry
                m, l, o = consume(k_blk, v_blk, s, m, l, o)
                k_blk, v_blk = rotate(k_blk, v_blk)
                return (k_blk, v_blk, m, l, o), None

            (k_l, v_l, m, l, o), _ = jax.lax.scan(
                fstep, (k_loc, v_loc, m0, l0, o0), jnp.arange(n - 1))
            m, l, o = consume(k_l, v_l, n - 1, m, l, o)
            return normalize(l, o)

        if causal and zigzag and n > 1:
            # Balanced zigzag fast path. Device idx holds real blocks
            # (idx, 2n-1-idx); for a foreign block from origin o != idx
            # only HALF the score tile can ever be unmasked, and that
            # half needs NO mask at all:
            #   o < idx: every local q position exceeds o's low half's
            #     positions and precedes its high half's -> compute
            #     q_all x k_lo, skip k_hi entirely;
            #   o > idx: only the local high half attends, and it
            #     covers BOTH halves of o's block -> q_hi x k_all.
            # The self tile (s=0) keeps the in-block causal mask. Per
            # rotation wall-clock is one HALF tile on every device
            # (vs a full tile on the worst device under the contiguous
            # skip), so attention wall time drops ~2x at large n —
            # counted in tiles; no chip run has timed it (ROADMAP A9).
            h = Tq // 2
            m, l, o = accumulate(k_loc, v_loc, 0, m0, l0, o0)

            def half_earlier(args):
                k_blk, v_blk, m, l, o = args
                kh = k_blk[:, :h].transpose(0, 2, 1, 3)   # [B, H, h, D]
                vh = v_blk[:, :h].transpose(0, 2, 1, 3)
                scores = jnp.einsum("bhqd,bhkd->bhqk", qh, kh,
                                    preferred_element_type=jnp.float32)
                return online_update(scores, vh, m, l, o)

            def half_later(args):
                k_blk, v_blk, m, l, o = args
                kh = k_blk.transpose(0, 2, 1, 3)          # [B, H, Tq, D]
                vh = v_blk.transpose(0, 2, 1, 3)
                scores = jnp.einsum("bhqd,bhkd->bhqk", qh[:, :, h:], kh,
                                    preferred_element_type=jnp.float32)
                m_hi, l_hi, o_hi = online_update(
                    scores, vh, m[:, :, h:], l[:, :, h:], o[:, :, h:])
                return (jnp.concatenate([m[:, :, :h], m_hi], 2),
                        jnp.concatenate([l[:, :, :h], l_hi], 2),
                        jnp.concatenate([o[:, :, :h], o_hi], 2))

            def step(carry, s):
                k_blk, v_blk, m, l, o = carry
                k_blk, v_blk = rotate(k_blk, v_blk)
                kv_origin = (idx - s) % n
                m, l, o = jax.lax.cond(
                    kv_origin < idx, half_earlier, half_later,
                    (k_blk, v_blk, m, l, o))
                return (k_blk, v_blk, m, l, o), None

            (_, _, m, l, o), _ = jax.lax.scan(
                step, (k_loc, v_loc, m, l, o), jnp.arange(1, n))
            return normalize(l, o)

        def step(carry, s):
            k_blk, v_blk, m, l, o = carry
            if causal and not zigzag:
                # contiguous placement: blocks from later devices are
                # fully masked — skip their score/accumulate compute
                # entirely
                kv_origin = (idx - s) % n
                m, l, o = jax.lax.cond(
                    kv_origin <= idx,
                    lambda a: accumulate(*a),
                    lambda a: (a[3], a[4], a[5]),
                    (k_blk, v_blk, s, m, l, o))
            else:
                m, l, o = accumulate(k_blk, v_blk, s, m, l, o)
            # rotate the K/V block around the ring
            k_blk, v_blk = rotate(k_blk, v_blk)
            return (k_blk, v_blk, m, l, o), None

        # n-1 steps rotate; the last block is consumed without the (dead)
        # final rotation, saving 2 collectives per layer per step.
        (k_l, v_l, m, l, o), _ = jax.lax.scan(
            step, (k_loc, v_loc, m0, l0, o0), jnp.arange(n - 1))
        m, l, o = accumulate(k_l, v_l, n - 1, m, l, o)
        return normalize(l, o)

    return jax.shard_map(local, mesh=mesh,
                         in_specs=(spec, spec, spec),
                         out_specs=spec,
                         check_vma=not flash_interpret)(q, k, v)


def full_attention_reference(q, k, v, causal=False, scale=None):
    """Unsharded reference implementation (tests / single device)."""
    if scale is None:
        scale = 1.0 / np.sqrt(q.shape[-1])
    qh = (q * scale).transpose(0, 2, 1, 3)
    kh = k.transpose(0, 2, 1, 3)
    vh = v.transpose(0, 2, 1, 3)
    scores = jnp.einsum("bhqd,bhkd->bhqk", qh, kh,
                        preferred_element_type=jnp.float32)
    if causal:
        T = q.shape[1]
        mask = jnp.tril(jnp.ones((T, T), bool))
        scores = jnp.where(mask[None, None], scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, vh.astype(jnp.float32))
    return out.transpose(0, 2, 1, 3).astype(q.dtype)
