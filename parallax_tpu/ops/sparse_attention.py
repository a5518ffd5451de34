"""Grouped-query attention under a learned top-k key selection.

A lightweight *indexer* scores every causal key of a query; the query
attends only the ``topk`` keys of largest score (all of its keys while
it has fewer), exactly: no approximate top-k, no selection by block.
The form is DeepSeek-V3.2's sparse attention laid over GQA, as the
``sa_config`` of Keye-VL-2.0's language model declares it
(``models/keye_vl2.py`` is the caller).

For a query ``t`` and a key ``s <= t``::

    I[t, s] = Hi^-1/2 * Di^-1/2 * sum_j w[t, j] * relu(qI[t, j] . kI[s])
    S_t     = the topk keys of largest I[t, .], ties to the lower s
    o[t, h] = sum_{s in S_t} softmax_{S_t}(q[t, h] . k[s, g(h)] / sqrt(D)) v[s, g(h)]

with ``g(h) = h // (Hq / Hkv)``: the query heads of a group read their
one key/value head where it lies, K and V are never repeated in memory.

The indexer learns from its own loss alone::

    L = sum_t KL(P_t || softmax_{S_t}(I[t, .]))

``P_t`` being the main attention's probabilities over ``S_t`` summed
over the query heads and normalised to one, under ``stop_gradient``.
The caller hands the indexer's inputs over already cut off from the
model (``stop_gradient`` on the block's input), so the language-model
loss sees ``S_t`` as a constant and the indexer sees only ``L``.

How it runs: queries in chunks of ``q_chunk`` (the config's
``q_chunk_size``), the chunks in bands of ``chunks_per_band``: a band
reads the keys up to its own end by a static slice, so most of the
causal half above the diagonal is never computed (a sixth of the work
is, at 16 chunks in bands of 4), and the chunks of a band share one
compiled body (``lax.map``). The ``[q_chunk, keys]`` float32 scores of
one chunk are the largest score array alive. The selection is a
threshold, not a sort: the ``topk``-th largest score of a row is found
exactly by a bitwise search over the scores' ordered 32-bit images (32
counts over the chunk), and ties at the threshold go to the lowest keys
by a running count, which is what ``jax.lax.top_k`` returns. A chunk's backward
pass is written out (``_chunk_bwd``): the forward keeps the chunk's
selection, output and logsumexp (named ``sparse_attn_chunk`` for a
caller's checkpoint policy) and the backward computes the scores again
(one fusion down to ``[q_chunk, keys]``, as in the forward), takes the
loss's gradient with respect to them on arrays of that shape, and only
then goes back through the per-head products.

Two executors, chosen as ``ops/pallas_lstm`` chooses its own (``impl``;
by the backend when not given), serve the attention over a chunk's
selection and the scores' backward alike. ``"kernel"`` (the TPU): four
Mosaic kernels that stream the keys through VMEM tile by tile, the
``[queries, keys]`` logits of a head never leaving the core. Three
attend under the chunk's selection mask: ``sparse_attn_fwd`` (online
softmax; output and per-row logsumexp), ``sparse_attn_bwd`` (one pass
over the key tiles gives dk and dv of the tile and accumulates dq, the
eight query heads of a group looping inside the kernel over one fetch
of their key/value head and of the mask) and ``sparse_attn_probs`` (the
heads' probabilities summed, the indexer's target). The target is made
once a pass: in the forward by ``sparse_attn_probs``, for the loss's
value (it needs each row's final logsumexp, which ``sparse_attn_fwd``
has only at its end); in the backward by ``sparse_attn_bwd`` itself,
which computes every head's probabilities on every key tile for the
gradient anyway and sums them, in ``sparse_attn_probs``' order, into a
second output: the backward runs no probabilities pass of its own, and
the forward keeps nothing more for it. The fourth,
``indexer_bwd``, is the gradient of ``indexer_scores``: for a key tile
and each indexer head in turn the products ``z [q_chunk, tile]`` again,
what ``relu`` and the head's weight let through of the scores'
cotangent, and its three sums (``dqi`` accumulated over the tiles,
``dki`` of the tile, ``dwi``); a chunk's per-head products ``[q_chunk,
Hi, keys]`` never reach HBM. Key tiles that lie wholly above the
chunk's diagonal are neither fetched nor computed. ``"xla"`` (elsewhere,
and the reference for the kernels' tests): einsum, softmax, einsum, and
``jax.vjp(indexer_scores)``.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl

_NEG = -1e30
# what a caller's checkpoint policy keeps so that no chunk runs twice: its
# selection, output and logsumexp
KEPT = "sparse_attn_chunk"


class SparseAttnOut(NamedTuple):
    out: jax.Array            # [B, T, Hq, D], the dtype of ``v``
    indexer_loss: jax.Array   # scalar f32: sum over (batch, query) of KL
    selected: jax.Array       # scalar f32: (query, key) pairs attended
    causal: jax.Array         # scalar f32: (query, key) pairs with s <= t
    selection: Optional[jax.Array] = None   # bool [B, T, T], on request


def indexer_scores(qi: jax.Array, ki: jax.Array, wi: jax.Array) -> jax.Array:
    """``I[b, t, s]`` in float32 for queries ``qi [B, Tq, Hi, Di]``,
    keys ``ki [B, Tk, Di]`` (one shared key head) and head weights
    ``wi [B, Tq, Hi]``. The products accumulate in float32 whatever the
    inputs' dtype; a score of ``-0.0`` is written ``+0.0``, so that the
    order of equal scores is the order of their keys on every path."""
    hi, di = qi.shape[-2], qi.shape[-1]
    z = jnp.einsum("bqjd,bsd->bqjs", qi, ki,
                   preferred_element_type=jnp.float32)
    s = jnp.sum(wi.astype(jnp.float32)[..., None] * jax.nn.relu(z), axis=2)
    s = s * jnp.float32(hi ** -0.5 * di ** -0.5)
    return jnp.where(s == 0, jnp.float32(0), s)


def _ordered_bits(x: jax.Array) -> jax.Array:
    """float32 -> uint32, monotone: ``a < b`` iff ``bits(a) < bits(b)``."""
    u = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    sign = u >> 31
    return jnp.where(sign == 1, ~u, u | jnp.uint32(0x80000000))


def select_topk(scores: jax.Array, valid: jax.Array, topk: int) -> jax.Array:
    """The exact top-``topk`` of each row of ``scores [..., K]`` among
    its ``valid`` keys, as a boolean mask; ties to the lower key, as
    ``jax.lax.top_k``; every valid key of a row that has at most
    ``topk`` of them."""
    bits = jnp.where(valid, _ordered_bits(scores), jnp.uint32(0))
    k = jnp.asarray(topk, jnp.int32)     # static or traced

    def refine(i, thr):
        cand = thr | (jnp.uint32(1) << (jnp.uint32(31) - i.astype(jnp.uint32)))
        enough = jnp.sum(bits >= cand[..., None], axis=-1,
                         dtype=jnp.int32) >= k
        return jnp.where(enough, cand, thr)

    # the largest threshold that still keeps topk keys: the topk-th
    # largest image of the row (0 where the row has fewer valid keys)
    thr = jax.lax.fori_loop(0, 32, refine,
                            jnp.zeros(bits.shape[:-1], jnp.uint32))
    above = bits > thr[..., None]
    at = bits == thr[..., None]
    room = k - jnp.sum(above, axis=-1, dtype=jnp.int32)
    rank = jnp.cumsum(at.astype(jnp.int32), axis=-1)
    return (above | (at & (rank <= room[..., None]))) & valid


# ---------------------------------------------------------------------------
# The attention over one chunk's selection: the einsum executor and the
# three kernels.
# ---------------------------------------------------------------------------

# a per-row scalar crosses a kernel's boundary broadcast over 8 lanes:
# a block's last two dims must be (8k, 128m) or the array's own
# (ops/pallas_attention.py has the story)
_LANES = 8
_KEY_TILE = 512


def _attend_xla(q, k, v, sel):
    """``(out [B, Hkv, R, C, D], target [B, C, Tk] f32)`` by einsum,
    softmax, einsum: the executor off the TPU, and the kernels'
    reference."""
    D = q.shape[-1]
    logits = jnp.einsum("bgrqd,bgsd->bgrqs", q, k,
                        preferred_element_type=jnp.float32)
    logits = jnp.where(sel[:, None, None],
                       logits * jnp.float32(D ** -0.5), _NEG)
    probs = jax.nn.softmax(logits, axis=-1)                     # f32
    out = jnp.einsum("bgrqs,bgsd->bgrqd", probs.astype(v.dtype), v)
    return out, jnp.sum(probs, axis=(1, 2)) / (q.shape[1] * q.shape[2])


def _scaled(q):
    """The kernels take the queries already scaled: one pass over them,
    not one over every tile of logits."""
    scale = jnp.float32(q.shape[-1] ** -0.5)
    return (q.astype(jnp.float32) * scale).astype(q.dtype)


def _kernel_operands(q, sel, q_start):
    """What every kernel call of a chunk is handed besides keys and
    values: the scaled queries, the selection as int8, the chunk's
    first position for the scalar core."""
    return (_scaled(q), sel.astype(jnp.int8),
            jnp.reshape(q_start, (1,)).astype(jnp.int32))


def _last_tile(start_ref, C: int, tk: int):
    """The last key tile a chunk starting at ``start_ref[0]`` can see."""
    return (start_ref[0] + C - 1) // tk


def _seen(kt, start_ref, C: int, tk: int):
    """The key tile a grid step fetches: a tile past the chunk's last
    is not fetched, its index folds onto the last needed one."""
    return jnp.minimum(kt, _last_tile(start_ref, C, tk))


def _tile(Tk: int) -> int:
    tk = min(_KEY_TILE, Tk)
    while Tk % tk:
        tk //= 2
    return tk


def _state_lanes(tk: int) -> int:
    """How many lanes hold a row's running maximum and sum in
    ``sparse_attn_fwd``: one lane tile where the key tile is made of
    them, the key tile's own width where it is not."""
    return 128 if tk % 128 == 0 else tk


def _over_lanes(x, n: int):
    """``x [rows, lanes]``, whose lanes all hold their row's one value,
    over ``n`` lanes: lane tiles side by side, no broadcast out of one
    lane."""
    lanes = x.shape[1]
    if n <= lanes:
        return x[:, :n]
    if n % lanes == 0:
        return jnp.tile(x, (1, n // lanes))
    return jnp.broadcast_to(x[:, :1], (x.shape[0], n))


def _fwd_kernel(start_ref, q_ref, k_ref, v_ref, mask_ref, o_ref, lse_ref,
                m_sc, l_sc, acc_sc, *, tk: int):
    """The online softmax's state of a row lies across the lanes: the
    running maximum ``m_sc [R, C, lanes]`` the same in every lane (what
    a reduction over the keys leaves, so ``s - m`` and ``alpha * acc``
    are lane tile against lane tile), the running sum ``l_sc`` as one
    partial sum a lane, summed across the lanes once a chunk."""
    R, C, D = q_ref.shape[2], q_ref.shape[3], q_ref.shape[4]
    lanes = m_sc.shape[2]
    kt = pl.program_id(2)

    @pl.when(kt == 0)
    def _():
        m_sc[...] = jnp.full(m_sc.shape, _NEG, jnp.float32)
        l_sc[...] = jnp.zeros(l_sc.shape, jnp.float32)
        acc_sc[...] = jnp.zeros(acc_sc.shape, jnp.float32)

    @pl.when(kt <= _last_tile(start_ref, C, tk))
    def _():
        k, v = k_ref[0, 0], v_ref[0, 0]
        keep = mask_ref[0] != 0                                  # [C, tk]
        for r in range(R):
            s = jnp.where(keep, jax.lax.dot_general(
                q_ref[0, 0, r], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32), _NEG)
            m_prev = m_sc[r]                                     # [C, lanes]
            m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            # a row that has met no selected key yet (m_next = _NEG)
            # gathers exp(0) here; the first real key's alpha = exp(_NEG
            # - m) = 0 wipes it, and every row has its own key to meet
            p = jnp.exp(s - _over_lanes(m_next, tk))
            alpha = jnp.exp(m_prev - m_next)
            l_sc[r] = alpha * l_sc[r] + sum(
                p[:, i:i + lanes] for i in range(0, tk, lanes))
            acc_sc[r] = _over_lanes(alpha, D) * acc_sc[r] \
                + jax.lax.dot_general(
                    p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
            m_sc[r] = m_next

    @pl.when(kt == pl.num_programs(2) - 1)
    def _():
        for r in range(R):
            l = jnp.maximum(jnp.sum(l_sc[r], axis=1, keepdims=True), 1e-30)
            o_ref[0, 0, r] = (acc_sc[r] / l).astype(o_ref.dtype)
            lse_ref[0, 0, r] = _over_lanes(m_sc[r], _LANES) + jnp.log(l)


def _bwd_kernel(start_ref, q_ref, k_ref, v_ref, mask_ref, do_ref, lse_ref,
                delta_ref, dq_ref, dk_ref, dv_ref, t_ref, dq_sc, *, tk: int):
    """One key tile of one group, the grid in ``sparse_attn_probs``'
    order (batch, key tile, group): the heads' probabilities ``p``,
    which the gradient computes anyway, are summed into the tile's block
    of the indexer's target as that kernel sums them, group after group
    and head after head. dq of every group accumulates in ``dq_sc``
    across the tiles and leaves at the last one."""
    R, C = q_ref.shape[2], q_ref.shape[3]
    kt, g = pl.program_id(1), pl.program_id(2)
    needed = kt <= _last_tile(start_ref, C, tk)

    @pl.when(kt == 0)
    def _():
        dq_sc[g] = jnp.zeros(dq_sc.shape[1:], jnp.float32)

    @pl.when(g == 0)
    def _():
        t_ref[0] = jnp.zeros(t_ref.shape[1:], jnp.float32)

    @pl.when(needed)
    def _():
        k, v = k_ref[0, 0], v_ref[0, 0]
        keep = mask_ref[0] != 0
        dk = jnp.zeros(dk_ref.shape[2:], jnp.float32)
        dv = jnp.zeros(dv_ref.shape[2:], jnp.float32)
        total = jnp.zeros(t_ref.shape[1:], jnp.float32)
        for r in range(R):
            q, do = q_ref[0, 0, r], do_ref[0, 0, r]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            p = jnp.where(keep, jnp.exp(s - lse_ref[0, 0, r][:, :1]), 0.0)
            total = total + p
            dp = jax.lax.dot_general(
                do, v, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            ds = (p * (dp - delta_ref[0, 0, r][:, :1])).astype(q.dtype)
            dv = dv + jax.lax.dot_general(
                p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dk = dk + jax.lax.dot_general(
                ds, q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dq_sc[g, r] = dq_sc[g, r] + jax.lax.dot_general(
                ds, k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        dk_ref[0, 0] = dk.astype(dk_ref.dtype)
        dv_ref[0, 0] = dv.astype(dv_ref.dtype)
        t_ref[0] = t_ref[0] + total

    @pl.when(jnp.logical_not(needed))
    def _():
        dk_ref[0, 0] = jnp.zeros(dk_ref.shape[2:], dk_ref.dtype)
        dv_ref[0, 0] = jnp.zeros(dv_ref.shape[2:], dv_ref.dtype)

    @pl.when(kt == pl.num_programs(1) - 1)
    def _():
        dq_ref[0, g] = dq_sc[g].astype(dq_ref.dtype)


def _probs_kernel(start_ref, q_ref, k_ref, mask_ref, lse_ref, out_ref, *,
                  tk: int):
    R, C = q_ref.shape[2], q_ref.shape[3]
    kt, g = pl.program_id(1), pl.program_id(2)

    @pl.when(g == 0)
    def _():
        out_ref[0] = jnp.zeros(out_ref.shape[1:], jnp.float32)

    @pl.when(kt <= _last_tile(start_ref, C, tk))
    def _():
        k = k_ref[0, 0]
        keep = mask_ref[0] != 0
        total = jnp.zeros(out_ref.shape[1:], jnp.float32)
        for r in range(R):
            s = jax.lax.dot_general(
                q_ref[0, 0, r], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            total = total + jnp.where(
                keep, jnp.exp(s - lse_ref[0, 0, r][:, :1]), 0.0)
        out_ref[0] = out_ref[0] + total


def _specs(q, k, tk: int, grid_order: str, fold_groups: bool):
    """Block specs of the chunk's operands for a grid over (batch,
    key/value head, key tile) (``"bgk"``) or (batch, key tile, key/value
    head) (``"bkg"``). A key tile past the chunk's last is not fetched:
    its index folds onto the last needed one. ``fold_groups`` (``"bkg"``
    only): the steps past the chunk's last tile read the last group's
    inputs, which the step before them read, so that they fetch nothing
    at all; the outputs stay each group's own."""
    B, Hkv, R, C, D = q.shape

    def ix(fn, fold=fold_groups):
        if grid_order == "bgk":
            return lambda b, g, kt, start: fn(b, g, kt, start)
        if fold:
            return lambda b, kt, g, start: fn(
                b, jnp.where(kt <= _last_tile(start, C, tk), g, Hkv - 1),
                kt, start)
        return lambda b, kt, g, start: fn(b, g, kt, start)

    def seen(kt, start):
        return _seen(kt, start, C, tk)

    return {
        "q": pl.BlockSpec((1, 1, R, C, D), ix(lambda b, g, kt, s:
                                              (b, g, 0, 0, 0))),
        "q_all": pl.BlockSpec((1, Hkv, R, C, D), ix(lambda b, g, kt, s:
                                                    (b, 0, 0, 0, 0))),
        "kv": pl.BlockSpec((1, 1, tk, D), ix(lambda b, g, kt, s:
                                             (b, g, seen(kt, s), 0))),
        "kv_out": pl.BlockSpec((1, 1, tk, D), ix(lambda b, g, kt, s:
                                                 (b, g, kt, 0), fold=False)),
        "mask": pl.BlockSpec((1, C, tk), ix(lambda b, g, kt, s:
                                            (b, 0, seen(kt, s)))),
        "row": pl.BlockSpec((1, 1, R, C, _LANES), ix(
            lambda b, g, kt, s: (b, g, 0, 0, 0))),
        "probs": pl.BlockSpec((1, C, tk), ix(lambda b, g, kt, s:
                                             (b, 0, kt))),
    }


def _call(kernel, name, q, k, grid_order, in_keys, out_keys, out_shapes,
          scratch, operands, interpret, *, fold_groups=False,
          semantics=("parallel", "parallel", "arbitrary")):
    from jax.experimental.pallas import tpu as pltpu
    B, Hkv, R, C, D = q.shape
    Tk = k.shape[2]
    tk = _tile(Tk)
    specs = _specs(q, k, tk, grid_order, fold_groups)
    grid = (B, Hkv, Tk // tk) if grid_order == "bgk" \
        else (B, Tk // tk, Hkv)
    return pl.pallas_call(
        functools.partial(kernel, tk=tk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid,
            in_specs=[specs[key] for key in in_keys],
            out_specs=[specs[key] for key in out_keys],
            scratch_shapes=scratch),
        out_shape=out_shapes,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=semantics,
            vmem_limit_bytes=64 * 1024 * 1024),
        name=name, interpret=interpret)(*operands)


def _fwd_call(q, k, v, mask, start, interpret):
    from jax.experimental.pallas import tpu as pltpu
    B, Hkv, R, C, D = q.shape
    lanes = _state_lanes(_tile(k.shape[2]))
    return _call(
        _fwd_kernel, "sparse_attn_fwd", q, k, "bgk",
        ("q", "kv", "kv", "mask"), ("q", "row"),
        [jax.ShapeDtypeStruct(q.shape, q.dtype),
         jax.ShapeDtypeStruct((B, Hkv, R, C, _LANES), jnp.float32)],
        [pltpu.VMEM((R, C, lanes), jnp.float32),
         pltpu.VMEM((R, C, lanes), jnp.float32),
         pltpu.VMEM((R, C, D), jnp.float32)],
        (start, q, k, v, mask), interpret)


def _bwd_call(q, k, v, mask, start, do, lse, delta, interpret):
    """``(dq, dk, dv, target)``: the gradient of the attention under
    the output's cotangent ``do`` (``delta``: each row's ``do . out``),
    and the indexer's target, the heads' probabilities summed and
    normalised to one, equal to what ``_probs_call`` gives on the same
    operands: the kernel sums the ``p`` its gradient is made of."""
    from jax.experimental.pallas import tpu as pltpu
    B, Hkv, R, C, D = q.shape
    dq, dk, dv, target = _call(
        _bwd_kernel, "sparse_attn_bwd", q, k, "bkg",
        ("q", "kv", "kv", "mask", "q", "row", "row"),
        ("q_all", "kv_out", "kv_out", "probs"),
        [jax.ShapeDtypeStruct(q.shape, q.dtype),
         jax.ShapeDtypeStruct(k.shape, k.dtype),
         jax.ShapeDtypeStruct(v.shape, v.dtype),
         jax.ShapeDtypeStruct((B, C, k.shape[2]), jnp.float32)],
        [pltpu.VMEM((Hkv, R, C, D), jnp.float32)],
        (start, q, k, v, mask, do, lse, delta), interpret,
        fold_groups=True, semantics=("parallel", "arbitrary", "arbitrary"))
    return dq, dk, dv, target / (Hkv * R)


def _probs_call(q, k, mask, start, lse, interpret):
    """The heads' probabilities summed and normalised to one."""
    B, Hkv, R, C, D = q.shape
    return _call(
        _probs_kernel, "sparse_attn_probs", q, k, "bkg",
        ("q", "kv", "mask", "row"), ("probs",),
        [jax.ShapeDtypeStruct((B, C, k.shape[2]), jnp.float32)],
        [], (start, q, k, mask, lse), interpret)[0] / (Hkv * R)


def _indexer_bwd_kernel(start_ref, qi_ref, ki_ref, wi_ref, ds_ref, dqi_ref,
                        dki_ref, dwi_ref, dqi_sc, dwi_sc, *, tk: int,
                        scale: float):
    """One key tile of ``indexer_scores``' gradient: for each head the
    products ``z [C, tk]`` again, what their ``relu`` and the head's
    weight let through of ``ds``, and the three sums it feeds. Nothing
    of ``[C, Hi, tk]`` leaves the core."""
    Hi, C = qi_ref.shape[1], qi_ref.shape[2]
    lanes = dwi_sc.shape[2]
    kt = pl.program_id(1)
    needed = kt <= _last_tile(start_ref, C, tk)

    @pl.when(kt == 0)
    def _():
        dqi_sc[...] = jnp.zeros(dqi_sc.shape, jnp.float32)
        dwi_sc[...] = jnp.zeros(dwi_sc.shape, jnp.float32)

    @pl.when(needed)
    def _():
        ki = ki_ref[0]                                           # [tk, Di]
        g = ds_ref[0] * jnp.float32(scale)                       # [C, tk]
        wi = wi_ref[0].astype(jnp.float32)                       # [C, Hi]
        dki = jnp.zeros(dki_ref.shape[1:], jnp.float32)
        for j in range(Hi):
            qi = qi_ref[0, j]                                    # [C, Di]
            z = jax.lax.dot_general(
                qi, ki, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            # dwi's sum over the keys: lane tile onto lane tile here,
            # across the lanes once a chunk
            p = jnp.maximum(z, 0.0) * g
            dwi_sc[j] = dwi_sc[j] + sum(
                p[:, i:i + lanes] for i in range(0, tk, lanes))
            dz = jnp.where(z > 0, wi[:, j:j + 1] * g, 0.0).astype(qi.dtype)
            dki = dki + jax.lax.dot_general(
                dz, qi, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dqi_sc[j] = dqi_sc[j] + jax.lax.dot_general(
                dz, ki, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        dki_ref[0] = dki.astype(dki_ref.dtype)

    @pl.when(jnp.logical_not(needed))
    def _():
        dki_ref[0] = jnp.zeros(dki_ref.shape[1:], dki_ref.dtype)

    @pl.when(kt == pl.num_programs(1) - 1)
    def _():
        dqi_ref[0] = dqi_sc[...].astype(dqi_ref.dtype)
        head = jax.lax.broadcasted_iota(jnp.int32, (C, Hi), 1)
        dwi = jnp.zeros((C, Hi), jnp.float32)
        for j in range(Hi):
            dwi = jnp.where(head == j, jnp.sum(dwi_sc[j], axis=1,
                                               keepdims=True), dwi)
        dwi_ref[0] = dwi.astype(dwi_ref.dtype)


def _indexer_bwd_call(qi, ki, wi, ds, start, interpret):
    """``(dqi, dki, dwi)`` of ``indexer_scores(qi, ki, wi)`` under the
    cotangent ``ds [B, C, Tk]`` (float32, zero wherever the chunk's
    queries see no key), by the kernel ``indexer_bwd``: the key tiles
    stream through VMEM as in ``sparse_attn_bwd``, the heads loop inside
    over one fetch of the tile. ``start``: the chunk's first position as
    ``_kernel_operands`` hands it to every kernel."""
    from jax.experimental.pallas import tpu as pltpu
    B, C, Hi, Di = qi.shape
    Tk = ki.shape[1]
    tk = _tile(Tk)

    dqi, dki, dwi = pl.pallas_call(
        functools.partial(_indexer_bwd_kernel, tk=tk,
                          scale=Hi ** -0.5 * Di ** -0.5),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(B, Tk // tk),
            in_specs=[
                pl.BlockSpec((1, Hi, C, Di), lambda b, kt, s: (b, 0, 0, 0)),
                pl.BlockSpec((1, tk, Di), lambda b, kt, s:
                             (b, _seen(kt, s, C, tk), 0)),
                pl.BlockSpec((1, C, Hi), lambda b, kt, s: (b, 0, 0)),
                pl.BlockSpec((1, C, tk), lambda b, kt, s:
                             (b, 0, _seen(kt, s, C, tk)))],
            out_specs=[
                pl.BlockSpec((1, Hi, C, Di), lambda b, kt, s: (b, 0, 0, 0)),
                pl.BlockSpec((1, tk, Di), lambda b, kt, s: (b, kt, 0)),
                pl.BlockSpec((1, C, Hi), lambda b, kt, s: (b, 0, 0))],
            scratch_shapes=[pltpu.VMEM((Hi, C, Di), jnp.float32),
                            pltpu.VMEM((Hi, C, min(tk, 128)), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((B, Hi, C, Di), qi.dtype),
                   jax.ShapeDtypeStruct(ki.shape, ki.dtype),
                   jax.ShapeDtypeStruct(wi.shape, wi.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
        name="indexer_bwd", interpret=interpret)(
            start, jnp.swapaxes(qi, 1, 2), ki, wi, ds)
    # head-major inside the kernel (a head's queries are one tile of
    # rows); the transpositions are [C, Hi, Di], not [C, Hi, keys]
    return jnp.swapaxes(dqi, 1, 2), dki, dwi


def _selection(qi, ki, wi, q_start, topk):
    """``(scores [B, C, Tk] f32, causal, sel)`` of a chunk whose first
    query stands at ``q_start``."""
    scores = indexer_scores(qi, ki, wi)
    C, Tk = scores.shape[1], scores.shape[2]
    t = q_start + jax.lax.broadcasted_iota(jnp.int32, (C, Tk), 0)
    s = jax.lax.broadcasted_iota(jnp.int32, (C, Tk), 1)
    causal = (s <= t)[None]
    sel = select_topk(jax.lax.stop_gradient(scores), causal, topk)
    return scores, causal, sel


def _indexer_loss(scores, sel, target):
    """``sum_t KL(target_t || softmax over S_t of scores_t)``."""
    log_q = jax.nn.log_softmax(jnp.where(sel, scores, _NEG), axis=-1)
    return jnp.sum(jnp.where(
        sel & (target > 0),
        target * (jnp.log(jnp.maximum(target, 1e-37)) - log_q), 0.0))


@functools.partial(jax.custom_vjp, nondiff_argnums=(8,))
def _chunk(q, k, v, qi, ki, wi, q_start, topk, impl: str):
    """One chunk of queries, the first at position ``q_start``, against
    the keys ``[0, Tk)``: the selection, the attention over it, the
    indexer's loss. ``q [B, Hkv, R, C, D]`` (query heads grouped by
    their key/value head), ``k, v [B, Hkv, Tk, D]``. Returns ``(out, kl,
    (selected, causal), sel)``.

    Its backward pass is written out (``_chunk_bwd``): it keeps the
    selection, the output and the rows' logsumexp, and computes the
    scores and the heads' summed probabilities (the indexer's target)
    again, so that neither the per-head logits nor the indexer's
    per-head products of a chunk outlive the pass that made them (under
    ``"kernel"`` they are never in memory at all). Under ``"kernel"``
    the forward's target comes from ``sparse_attn_probs`` and the
    backward's from ``sparse_attn_bwd``, which sums the probabilities
    its gradient is made of; under ``"xla"`` both from the einsum
    executor's softmax."""
    return _chunk_fwd(q, k, v, qi, ki, wi, q_start, topk, impl)[0]


def _chunk_fwd(q, k, v, qi, ki, wi, q_start, topk, impl):
    # the indexer's own layer name (obs/xprof.LAYER_SCOPES): innermost
    # wins over the caller's `attention`
    with jax.named_scope("indexer"):
        scores, causal, sel = _selection(qi, ki, wi, q_start, topk)
    # what a caller's checkpoint policy may keep for the backward pass
    # in place of running the chunk again
    def keep(a):
        return checkpoint_name(a, KEPT)

    sel = keep(sel)
    if impl == "xla":
        out, target = _attend_xla(q, k, v, sel)
        out = keep(out)
        kept = (sel,)
    else:
        interpret = impl == "kernel_interpret"
        qs, mask, start = _kernel_operands(q, sel, q_start)
        out, lse = _fwd_call(qs, k, v, mask, start, interpret)
        out, lse = keep(out), keep(lse)
        target = _probs_call(qs, k, mask, start, lse, interpret)
        kept = (sel, out, lse)
    with jax.named_scope("indexer"):
        kl = _indexer_loss(scores, sel, target)
        counts = (jnp.sum(sel, dtype=jnp.float32),
                  jnp.sum(jnp.broadcast_to(causal, sel.shape),
                          dtype=jnp.float32))
    return (out, kl, counts, sel), (q, k, v, qi, ki, wi, q_start, kept)


def _chunk_bwd(impl, res, cotangents):
    q, k, v, qi, ki, wi, q_start, kept = res
    d_out, d_kl = cotangents[0], cotangents[1]
    sel = kept[0]
    if impl == "xla":
        (_, target), pull = jax.vjp(
            lambda q, k, v: _attend_xla(q, k, v, sel), q, k, v)
        dq, dk, dv = pull((d_out, jnp.zeros_like(target)))
    else:
        interpret = impl == "kernel_interpret"
        out, lse = kept[1:]
        qs, mask, start = _kernel_operands(q, sel, q_start)
        delta = jnp.sum(d_out.astype(jnp.float32) * out.astype(jnp.float32),
                        axis=-1, keepdims=True)
        delta = jnp.broadcast_to(delta, delta.shape[:-1] + (_LANES,))
        dqs, dk, dv, target = _bwd_call(
            qs, k, v, mask, start, d_out.astype(q.dtype), lse, delta,
            interpret)
        dq = _scaled(dqs)
    with jax.named_scope("indexer"):
        # the scores again, and the way back through the chunk's
        # per-head products
        if impl == "xla":
            scores, back = jax.vjp(indexer_scores, qi, ki, wi)
        else:
            scores = indexer_scores(qi, ki, wi)

            def back(ds):
                # a score of zero is written, not computed
                # (indexer_scores)
                return _indexer_bwd_call(
                    qi, ki, wi, jnp.where(scores == 0, 0.0, ds), start,
                    interpret)
        # between them the loss's side, on [C, keys] arrays alone: what
        # the loss asks of the scores (zero off the selection)
        _, pull = jax.vjp(lambda s: _indexer_loss(s, sel, target), scores)
        dqi, dki, dwi = back(pull(d_kl)[0])
    return dq, dk, dv, dqi, dki, dwi, None, None


_chunk.defvjp(_chunk_fwd, _chunk_bwd)


def sparse_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     qi: jax.Array, ki: jax.Array, wi: jax.Array, *,
                     topk: int, q_chunk: int = 512,
                     chunks_per_band: int = 4,
                     return_selection: bool = False,
                     impl: Optional[str] = None) -> SparseAttnOut:
    """Causal attention of ``q [B, T, Hq, D]`` over ``k, v [B, T, Hkv,
    D]``, each query reading the ``topk`` keys its indexer (``qi [B, T,
    Hi, Di]``, ``ki [B, T, Di]``, ``wi [B, T, Hi]``) scores highest.
    With ``topk >= T`` this is dense causal attention; ``topk`` may be
    a traced scalar (one compiled program then serves any). Differentiable
    in all six inputs: ``q, k, v`` receive the gradient of whatever
    reads ``out``; ``qi, ki, wi`` that of ``indexer_loss`` only.
    ``chunks_per_band`` trades compile time (one body a band) against
    work above the diagonal; ``impl`` names the attention's executor
    (the module's docstring). ``return_selection`` also hands back which
    keys each query read (``[B, T, T]`` booleans: for a comparison, not
    for training)."""
    B, T, Hq, D = q.shape
    Hkv = k.shape[2]
    if Hq % Hkv:
        raise ValueError(f"{Hq} query heads do not group over {Hkv} "
                         f"key/value heads")
    C = min(int(q_chunk), T)
    if T % C:
        raise ValueError(f"sequence length {T} is not a multiple of the "
                         f"query chunk {C}")
    if impl is None:
        impl = "kernel" if jax.default_backend() == "tpu" else "xla"
    if impl not in ("kernel", "kernel_interpret", "xla"):
        raise ValueError(f"unknown impl {impl!r}")
    n_chunks = T // C
    per_band = max(1, min(int(chunks_per_band), n_chunks))
    # heads grouped by their key/value head, time next to the head size:
    # q [B, Hkv, R, T, D], k and v [B, Hkv, T, D]
    qg = jnp.transpose(q.reshape(B, T, Hkv, Hq // Hkv, D), (0, 2, 3, 1, 4))
    kg, vg = jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2)
    topk = jnp.asarray(topk, jnp.int32)

    def chunks_of(a, lo, n, axis=1):
        """``n`` chunks of ``a`` from position ``lo`` on along ``axis``,
        stacked in front."""
        part = jax.lax.slice_in_dim(a, lo, lo + n * C, axis=axis)
        shape = part.shape[:axis] + (n, C) + part.shape[axis + 1:]
        return jnp.moveaxis(part.reshape(shape), axis, 0)

    outs, sels, kl, selected, causal = [], [], 0.0, 0.0, 0.0
    for first in range(0, n_chunks, per_band):
        n = min(per_band, n_chunks - first)
        lo, hi = first * C, (first + n) * C
        keys = (kg[:, :, :hi], vg[:, :, :hi], ki[:, :hi])

        def one(xs, keys=keys):
            q_c, qi_c, wi_c, q_start = xs
            return _chunk(q_c, keys[0], keys[1], qi_c, keys[2], wi_c,
                          q_start, topk, impl)

        o, kl_b, (sel_b, causal_b), sel = jax.lax.map(
            one, (chunks_of(qg, lo, n, axis=3), chunks_of(qi, lo, n),
                  chunks_of(wi, lo, n),
                  lo + C * jnp.arange(n, dtype=jnp.int32)))
        # [n, B, Hkv, R, C, D] -> [B, n * C, Hq, D]
        outs.append(jnp.transpose(o, (1, 0, 4, 2, 3, 5)).reshape(
            B, n * C, Hq, D))
        kl, selected, causal = (kl + jnp.sum(kl_b), selected + jnp.sum(sel_b),
                                causal + jnp.sum(causal_b))
        if return_selection:
            sel = jnp.moveaxis(sel, 0, 1).reshape(B, n * C, hi)
            sels.append(jnp.pad(sel, ((0, 0), (0, 0), (0, T - hi))))
    return SparseAttnOut(
        jnp.concatenate(outs, axis=1), kl, selected, causal,
        jnp.concatenate(sels, axis=1) if return_selection else None)
