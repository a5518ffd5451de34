"""Fused Pallas paged-attention decode kernel (TPU).

ISSUE 16 / ROADMAP open item 2: the serving stack's paged KV pool
(serve/paging.py) has genuinely sparse occupancy — a slot owns only
``ceil(cap / page_size)`` pages, prefix-shared COW pages multiply the
logical width further — but the einsum decode step executes it densely:
``models/nmt.py _decode_tokens_cached`` gathers the FULL page-table
width with ``jnp.take`` clip-then-mask, materializes ``[S, P *
page_size, D]`` K/V views in HBM, and reads them again inside the
attention einsums. Every decode step pays the dense buffer's traffic
whatever the pool actually holds.

This module is the Flash-Decoding / PagedAttention (vLLM lineage)
answer: one Pallas program per (slot, page-step) that reads the
``[S, P]`` page table directly (scalar prefetch — the table drives the
K/V BlockSpec index maps), streams one ``[page_size, D]`` K and V block
per live page through VMEM, and advances the online-softmax
``(m, l, acc)`` recurrence per head in VMEM scratch. No host-side
gather, no clip-then-mask, no full-width HBM read:

* a LIVE page entry DMAs exactly one K block and one V block;
* an OOB-sentinel entry (``pool_pages``, the unallocated marker) is
  masked IN-KERNEL — its index map clips to the previous block index
  shape-legally, and because consecutive equal block indices are not
  re-fetched, a sentinel tail past the last live page costs at most
  one redundant block, never the table width;
* the causal frontier (``pos`` per query) is applied in-kernel too, so
  stale data inside a reused page is exactly as invisible as it is on
  the einsum path.

Head handling: the pool layout is ``[pool_pages, page_size, D]`` with
``D = num_heads * head_dim`` fused in the trailing dim (the layout the
pool writes/COW copies already use). A per-head lane block
(``head_dim`` lanes) is Mosaic-illegal for ``head_dim < 128``, and a
head-split pool layout would force a full-pool transpose — the exact
full-width HBM read this kernel exists to delete. So each program
advances EVERY head's recurrence: per-head score/value dots run over
the full ``D`` width with head-masked operands (a column-iota mask
zeroes foreign heads' contributions). That spends ``num_heads`` x more
MACs than a head-sliced dot; decode attention is bandwidth-bound, so
the page stream — not the MXU — remains the bottleneck, and every
block shape satisfies Mosaic's equal-dims tiling rule at ANY
``head_dim``/``page_size`` (the r5 lesson, see
ops/pallas_attention._LANES).

Executor switch (the PR 14 ``pallas_lstm`` pattern): ``impl`` is one of

* ``'kernel'`` — require the Pallas kernel; loud ValueError when the
  per-program resident set cannot fit the VMEM budget
  (``PARALLAX_PAGED_ATTN_VMEM_BUDGET``, default 12 MiB) on a real
  TensorCore run (interpret mode runs any size);
* ``'einsum'`` — the gather-based reference (the exact
  ``models/nmt.py`` clip-then-mask math);
* ``'auto'`` (default) — kernel on TPU when it fits, einsum otherwise
  (off-TPU the kernel would only pay the interpreter tax).

The ``PARALLAX_PAGED_ATTN`` env var overrides the argument
(operational escape hatch, same three values, consulted at trace
time). ``resolve_impl`` exposes the decision so ``models/nmt.py``
can branch its trace once per signature.

Sentinel semantics have ONE owner here: ``sentinel_write_coords``
(write side — sentinel/overflow positions become OOB coordinates that
``.at[].set(mode='drop')`` discards) and ``paged_gather`` (read side —
clip-then-mask) are THE helpers both the einsum fallback in
``models/nmt.py`` and the kernel's reference/verify path use.

Contract note (tested in tests/test_paged_attn.py): the kernel masks
sentinel pages by PAGE, the einsum path masks by POSITION (clip makes
a sentinel entry gather a live page; the causal mask hides it). The
two agree on every query whose visible positions ``<= pos`` all lie in
live pages — the allocator invariant (pages cover a slot's whole cap
while in flight). A query with NO live visible position (the
zero-allocated-pages edge) emits exactly 0 from the kernel, never NaN;
its einsum counterpart reads clipped garbage. Both are discarded
host-side, and neither can leak into kept tokens: overshoot positions
are write-dropped, so the caches other queries read never contain
them.

A CPU run prices the Pallas interpreter, not the TPU memory system,
and is no statement about speed. The analytic ``kernel_hbm_bytes`` /
``gather_hbm_bytes`` table is counted from shapes, not measured; on
the chip the kernel was timed by hand only (0.3 % of its roofline,
``PERF.md`` section 6) and no benchmark cell runs it yet.
"""

from __future__ import annotations

import collections
import functools
import os
import weakref
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30
_LANES = 8      # lane-broadcast width for per-row scalars (see
                # ops/pallas_attention._LANES: (8, lanes) blocks satisfy
                # Mosaic's equal-dims clause at 1/16 the 128-lane cost)

# The flagship decode shape the lowering gate and the analytic byte
# table price: continuous serving of the transformer NMT flagship
# (D=512, 8 heads) with a 2048-position cap paged at 128 tokens/page,
# 64 slots, spec-decode verify width 3 (spec_tokens=2 + bonus).
FLAGSHIP_DECODE = dict(S=64, G=3, D=512, num_heads=8, page_size=128,
                       P=16, pool_pages=1024)


# -- sentinel semantics: the ONE owner both executors use -------------------


def sentinel_write_coords(pages, pos, page_size: int, pool_pages: int):
    """Write coordinates for scattering ``[S, G]`` new K/V positions
    through a ``[S, P]`` page table: position ``pos`` lands in page
    ``pages[s, pos // page_size]`` at offset ``pos % page_size``.

    Sentinel semantics (the write-side owner): an entry holding the OOB
    sentinel (``>= pool_pages``) or a position past the table width
    maps to page id ``pool_pages`` — out of bounds for the pool, so
    ``.at[pg, off].set(..., mode='drop')`` discards it. A slot can
    never corrupt a foreign page, and dropped positions are exactly
    those no slot ever reads back (serve/paging.py).

    Returns ``(pg [S, G], off [S, G])`` int32.
    """
    P = pages.shape[1]
    page_slot = pos // page_size
    pg = jnp.take_along_axis(pages, jnp.clip(page_slot, 0, P - 1),
                             axis=1)
    pg = jnp.where((page_slot < P) & (pg < pool_pages), pg, pool_pages)
    return pg, pos % page_size


def paged_gather(pool_layer, pages):
    """Clip-then-mask read gather (the read-side owner): materialize
    one slot-contiguous ``[S, P * page_size, D]`` view of a
    ``[pool_pages, page_size, D]`` pool layer through a ``[S, P]`` page
    table. Sentinel entries CLIP to a live page — callers MUST mask
    every gathered position beyond the slot's frontier (``pos <= t``)
    out of attention, which hides the clipped foreign data along with
    any stale content of reused live pages. This is the full-width
    traffic the kernel path deletes; it stays as the einsum fallback
    and the bit-identity reference."""
    pool, ps, D = pool_layer.shape
    S, P = pages.shape
    safe = jnp.clip(pages, 0, pool - 1)
    return jnp.take(pool_layer, safe, axis=0).reshape(S, P * ps, D)


# -- the kernel -------------------------------------------------------------


def _paged_attn_kernel(pages_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
                       m_ref, l_ref, acc_ref, *, page_size: int,
                       pool_pages: int, num_heads: int, num_queries: int,
                       sqrt_hd: float):
    """One (slot, page-step) program. Refs:

    * ``pages_ref [S, P]`` / ``pos_ref [S, G]`` — scalar prefetch
      (SMEM); the page table also drives the K/V index maps.
    * ``q_ref [1, Gp, D]`` — the slot's queries padded to a whole
      sublane tile (``_padded_queries``), VMEM-resident across the
      page sweep (constant index map). Rows ``>= G`` are padding: they
      see no position, accumulate nothing and are sliced off outside.
    * ``k_ref``/``v_ref [1, page_size, D]`` — THE streamed block: the
      index map fetched page ``pages[s, p]`` (clipped).
    * ``o_ref [1, Gp, D]`` — written at the last page step.
    * scratch: ``m_ref``/``l_ref [num_heads, Gp, _LANES]`` f32 and
      ``acc_ref [Gp, D]`` f32, persisting across the page sweep.
    """
    s, p = pl.program_id(0), pl.program_id(1)
    Gp = q_ref.shape[1]
    D = q_ref.shape[2]
    hd = D // num_heads

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    page_id = pages_ref[s, p]
    live = page_id < pool_pages
    q = q_ref[0]                                           # [Gp, D]
    k = k_ref[0]                                           # [ps, D]
    v = v_ref[0].astype(jnp.float32)

    # shared mask for this page step, built as whole int32 tiles: the
    # causal frontier per query row (per-row SMEM scalars selected in
    # by a row iota over the static real rows; padding rows keep -1 and
    # so see nothing) and the in-kernel sentinel kill (a dead page
    # moves the frontier to -1 for every row)
    row = jax.lax.broadcasted_iota(jnp.int32, (Gp, page_size), 0)
    tok = p * page_size + jax.lax.broadcasted_iota(
        jnp.int32, (Gp, page_size), 1)
    frontier = jnp.full((Gp, page_size), -1, jnp.int32)
    for g in range(num_queries):
        frontier = jnp.where(row == g, pos_ref[s, g], frontier)
    frontier = jnp.where(live, frontier, -1)
    visible = tok <= frontier                              # [Gp, ps]

    # column->head map for the head-masked full-width dots
    col_head = jax.lax.broadcasted_iota(jnp.int32, (Gp, D), 1) // hd

    acc = acc_ref[...]                                     # [Gp, D] f32
    contrib = jnp.zeros((Gp, D), jnp.float32)
    alpha_full = jnp.zeros((Gp, D), jnp.float32)
    for h in range(num_heads):
        head_cols = col_head == h
        q_h = jnp.where(head_cols, q, jnp.zeros_like(q))   # [Gp, D]
        # scale AFTER the f32 dot (divide, matching the reference's
        # ``scores / sqrt(hd)`` rounding) — scaling q in the compute
        # dtype would inject ~2^-9 relative score noise under bf16,
        # an order of magnitude past the online-softmax drift
        s_h = jax.lax.dot_general(
            q_h, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) / sqrt_hd  # [Gp, ps]
        s_h = jnp.where(visible, s_h, _NEG_INF)
        m_prev = m_ref[h]                                  # [Gp, LANES]
        l_prev = l_ref[h]
        m_cur = jnp.max(s_h, axis=-1, keepdims=True)       # [Gp, 1]
        m_new = jnp.maximum(m_prev, m_cur)                 # [Gp, LANES]
        alpha = jnp.exp(jnp.minimum(m_prev - m_new, 0.0))
        p_h = jnp.exp(s_h - m_new[:, :1])
        p_h = jnp.where(visible, p_h, 0.0)
        m_ref[h] = m_new
        l_ref[h] = l_prev * alpha + jnp.sum(p_h, axis=-1,
                                            keepdims=True)
        pv = jax.lax.dot_general(
            p_h, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)            # [Gp, D]
        contrib = contrib + jnp.where(head_cols, pv, 0.0)
        alpha_full = alpha_full + jnp.where(head_cols, alpha[:, :1],
                                            0.0)
    acc_ref[...] = acc * alpha_full + contrib

    @pl.when(p == pl.num_programs(1) - 1)
    def _finalize():
        l_full = jnp.zeros((Gp, D), jnp.float32)
        for h in range(num_heads):
            l_full = l_full + jnp.where(col_head == h, l_ref[h][:, :1],
                                        0.0)
        # a fully-masked query (zero live visible positions) has l == 0
        # and acc == 0: emit exactly 0, never NaN (module docstring)
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l_full, 1e-30)).astype(o_ref.dtype)


def _padded_queries(G: int, itemsize: int) -> int:
    """Query rows per program: ``G`` rounded up to one whole sublane
    tile of the compute dtype (8 rows of 32-bit, 16 of 16-bit). Mosaic
    refuses the sub-tile shapes — at G=1 ``Not implemented: Sublane
    broadcast``, at G=3 an internal ``native_vreg_ty`` assert (first
    compile for a TensorCore, PR 22) — and the MXU pads to the tile
    anyway, so the padding rows cost no extra passes."""
    tile = 8 * max(1, 4 // itemsize)
    return -(-G // tile) * tile


def _kernel_call(q, k_pool, v_pool, pages, pos, num_heads: int,
                 page_size: int, interpret: bool):
    S, G, D = q.shape
    pool = k_pool.shape[0]
    P = pages.shape[1]
    hd = D // num_heads
    Gp = _padded_queries(G, jnp.dtype(q.dtype).itemsize)
    kernel = functools.partial(
        _paged_attn_kernel, page_size=page_size, pool_pages=pool,
        num_heads=num_heads, num_queries=G, sqrt_hd=float(np.sqrt(hd)))

    def kv_map(s, p, pages_ref, pos_ref):
        # sentinel entries clip to the LAST live-clipped index
        # shape-legally; consecutive equal indices are not re-fetched,
        # so a sentinel tail costs at most one redundant block
        return (jnp.minimum(pages_ref[s, p], pool - 1), 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(S, P),
        in_specs=[
            pl.BlockSpec((1, Gp, D), lambda s, p, pages, pos: (s, 0, 0)),
            pl.BlockSpec((1, page_size, D), kv_map),
            pl.BlockSpec((1, page_size, D), kv_map),
        ],
        out_specs=pl.BlockSpec((1, Gp, D),
                               lambda s, p, pages, pos: (s, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((num_heads, Gp, _LANES), jnp.float32),  # m
            pltpu.VMEM((num_heads, Gp, _LANES), jnp.float32),  # l
            pltpu.VMEM((Gp, D), jnp.float32),                  # acc
        ],
    )
    q_pad = jnp.pad(q, ((0, 0), (0, Gp - G), (0, 0)))
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, Gp, D), q.dtype),
        interpret=interpret,
    )(pages.astype(jnp.int32), pos.astype(jnp.int32), q_pad, k_pool,
      v_pool)
    return out[:, :G]


# -- the einsum reference ---------------------------------------------------


def _einsum_reference(q, k_pool, v_pool, pages, pos, num_heads: int,
                      page_size: int):
    """The gather-based fallback: ``paged_gather`` clip-then-mask plus
    the per-query UNROLLED attention einsums — the exact
    ``models/nmt.py`` ``_decode_tokens_cached`` math (unrolling at
    Tq=1 keeps each query's reduction tiling identical to the
    single-token step; see the bit-identity note there)."""
    S, G, D = q.shape
    Tbuf = pages.shape[1] * page_size
    k_all = paged_gather(k_pool, pages)
    v_all = paged_gather(v_pool, pages)
    h = num_heads
    hd = D // h

    def one_query(g):
        mask = (jnp.arange(Tbuf)[None, :]
                <= pos[:, g][:, None])[:, None, None, :]
        qh = q[:, g:g + 1].reshape(S, 1, h, hd).transpose(0, 2, 1, 3)
        kh = k_all.reshape(S, Tbuf, h, hd).transpose(0, 2, 1, 3)
        vh = v_all.reshape(S, Tbuf, h, hd).transpose(0, 2, 1, 3)
        scores = jnp.einsum("bhqd,bhkd->bhqk", qh, kh,
                            preferred_element_type=jnp.float32) \
            / np.sqrt(hd)
        scores = jnp.where(mask, scores,
                           jnp.asarray(-1e9, scores.dtype))
        probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
        out = jnp.einsum("bhqk,bhkd->bhqd", probs.astype(q.dtype), vh)
        return out.transpose(0, 2, 1, 3).reshape(S, 1, D)

    outs = [one_query(g) for g in range(G)]
    return outs[0] if G == 1 else jnp.concatenate(outs, axis=1)


# -- executor switch --------------------------------------------------------


def _vmem_fit(G: int, D: int, page_size: int, num_heads: int,
              itemsize: int, budget: int) -> bool:
    """Whether one program's resident set fits, counted as the
    BlockSpecs allocate it: q and out blocks at the padded query
    width, double-buffered like the K/V page streams (their block
    index moves with the slot), and the f32 (m, l, acc) scratch with
    the ``_LANES`` dim padded out to a whole 128-lane tile."""
    Gp = _padded_queries(G, itemsize)
    resident = (2 * 2 * Gp * D * itemsize           # q + out blocks
                + 2 * 2 * page_size * D * itemsize  # k, v page streams
                + 2 * num_heads * Gp * 128 * 4      # m, l
                + Gp * D * 4)                       # acc
    return resident <= budget


def resolve_impl(impl: Optional[str], *, G: int, D: int,
                 page_size: int, num_heads: int, itemsize: int,
                 interpret: Optional[bool] = None) -> str:
    """Resolve the executor once per trace -> ``'kernel'`` or
    ``'einsum'``. The ``PARALLAX_PAGED_ATTN`` env var overrides the
    argument; ``'auto'`` picks the kernel on a real TensorCore run
    when the resident set fits the VMEM budget and the einsum gather
    otherwise (off-TPU the kernel would only pay the interpreter
    tax). An explicit ``'kernel'`` that cannot fit refuses loudly
    instead of failing deep inside Mosaic."""
    impl = os.environ.get("PARALLAX_PAGED_ATTN") or (impl or "auto")
    if impl not in ("auto", "kernel", "einsum"):
        raise ValueError(
            f"unknown paged-attention impl {impl!r}; expected 'auto', "
            f"'kernel' or 'einsum' (PARALLAX_PAGED_ATTN overrides)")
    if impl == "einsum":
        return "einsum"
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    budget = int(os.environ.get("PARALLAX_PAGED_ATTN_VMEM_BUDGET",
                                12 * 1024 * 1024))
    fit = _vmem_fit(G, D, page_size, num_heads, itemsize, budget)
    if impl == "kernel":
        if not fit and not interpret:
            raise ValueError(
                f"pallas paged attention: resident set (q/out [{G}, "
                f"{D}] + double-buffered [{page_size}, {D}] K/V pages "
                f"+ f32 accumulators) exceeds the {budget / 1e6:.0f} "
                f"MB VMEM budget — use impl='einsum' or a smaller "
                f"page_size")
        return "kernel"
    # auto
    if interpret or not fit:
        return "einsum"
    return "kernel"


def paged_decode_attention(q, k_pool, v_pool, pages, pos, *,
                           num_heads: int, page_size: int,
                           impl: str = "auto",
                           interpret: Optional[bool] = None,
                           mesh=None):
    """Paged self-attention for one decode step.

    ``q [S, G, D]`` (G = verify width, 1 for a plain step),
    ``k_pool``/``v_pool [pool_pages, page_size, D]`` (one layer of the
    serve pool), ``pages [S, P]`` int32 page table with OOB sentinel
    ``pool_pages`` marking unallocated entries, ``pos [S, G]`` int32
    absolute positions (query g attends to cache positions
    ``<= pos[s, g]``). Returns ``[S, G, D]`` in ``q.dtype``.

    Executor selection per the module docstring; every call records
    its static signature for the cost model (``trace_records``), like
    ops/pallas_lstm — XLA's cost_analysis prices a Pallas custom call
    at ~zero bytes, so without the records a kernel-served decode
    would score as HBM-free.
    """
    S, G, D = q.shape
    pool, ps, Dp = k_pool.shape
    if Dp != D or v_pool.shape != k_pool.shape:
        raise ValueError(
            f"pool shapes {k_pool.shape}/{v_pool.shape} do not match "
            f"q feature dim {D}")
    if ps != page_size:
        raise ValueError(
            f"page_size={page_size} != pool page dim {ps}")
    if D % num_heads:
        raise ValueError(f"model dim {D} not divisible by "
                         f"num_heads {num_heads}")
    if pos.shape != (S, G):
        raise ValueError(f"pos shape {pos.shape} != (S, G)=({S}, {G})")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    impl = resolve_impl(impl, G=G, D=D, page_size=page_size,
                        num_heads=num_heads,
                        itemsize=jnp.dtype(q.dtype).itemsize,
                        interpret=interpret)
    _record_call(mesh, S, G, D, num_heads, page_size, pages.shape[1],
                 pool, jnp.dtype(q.dtype).itemsize, impl)
    if impl == "einsum":
        return _einsum_reference(q, k_pool, v_pool, pages, pos,
                                 num_heads, page_size)
    return _kernel_call(q, k_pool, v_pool, pages, pos, num_heads,
                        page_size, bool(interpret))


# -- trace records for the cost model ---------------------------------------
# The ops/pallas_lstm pattern: every call records its static signature
# at trace time, deduped by (mesh, signature);
# tune/costmodel.inputs_from_engine reads the records for its engine's
# mesh and folds the analytic kernel bytes into the HBM roofline term.
# Only impl='kernel' records carry custom-call traffic XLA cannot see;
# einsum calls are priced by cost_analysis itself (the records still
# note them so calibration can tell which executor served a trace).

_TRACE_RECORDS: "collections.OrderedDict" = collections.OrderedDict()
_TRACE_RECORDS_MAX = 64


def _record_call(mesh, S, G, D, num_heads, page_size, P, pool_pages,
                 itemsize, impl):
    info = {"S": int(S), "G": int(G), "D": int(D),
            "num_heads": int(num_heads), "page_size": int(page_size),
            "P": int(P), "pool_pages": int(pool_pages),
            "itemsize": int(itemsize), "impl": str(impl)}
    key = (id(mesh) if mesh is not None else None,
           tuple(sorted(info.items())))
    try:
        ref = weakref.ref(mesh) if mesh is not None else None
    except TypeError:
        ref = (lambda m: (lambda: m))(mesh)
    _TRACE_RECORDS[key] = (ref, info)
    while len(_TRACE_RECORDS) > _TRACE_RECORDS_MAX:
        _TRACE_RECORDS.popitem(last=False)


def trace_records(mesh=None):
    """Recorded paged-attention call signatures for ``mesh`` (None:
    records made outside any mesh). Each dict carries S/G/D/num_heads/
    page_size/P/pool_pages/itemsize and ``impl`` — which executor
    served the trace ('kernel' | 'einsum'; only kernel calls are
    custom-call traffic cost_analysis cannot price)."""
    out = []
    for ref, info in _TRACE_RECORDS.values():
        m = ref() if ref is not None else None
        if (mesh is None and ref is None) or (m is mesh
                                              and m is not None):
            out.append(dict(info))
    return out


def reset_trace_records():
    _TRACE_RECORDS.clear()


# -- analytic HBM accounting ------------------------------------------------


def kernel_hbm_bytes(S, G, D, page_size, live_pages, itemsize,
                     num_layers: int = 1):
    """Analytic per-decode-step HBM bytes of the KERNEL path:
    ``live_pages`` is the TOTAL live page entries across all S page
    tables (occupancy x S x P). Each live entry streams one K and one
    V ``[page_size, D]`` block; q and out are one block per slot
    (+ at most one redundant clipped block per slot for a sentinel
    tail, excluded as noise). Exact for the kernel's block/stream
    structure; not a measurement."""
    stream = 2 * int(live_pages) * page_size * D * itemsize   # K + V
    qout = 2 * S * G * D * itemsize
    return {"stream_bytes": num_layers * stream,
            "qout_bytes": num_layers * qout,
            "total_bytes": num_layers * (stream + qout)}


def gather_hbm_bytes(S, G, D, page_size, P, itemsize,
                     num_layers: int = 1):
    """The einsum gather path's analytic bytes for the same shapes —
    the full-width story the kernel deletes: ``jnp.take`` reads the
    table-width pool pages (sentinels clip to a live page and still
    fetch), WRITES the ``[S, P * page_size, D]`` gathered K/V views,
    and the attention einsums read them again. Occupancy-independent:
    the dense buffer width is paid whatever the pool holds."""
    Tbuf = P * page_size
    gather_rw = 2 * 2 * S * Tbuf * D * itemsize   # K+V, read pool + write view
    attn_read = 2 * S * Tbuf * D * itemsize       # K+V views read by einsums
    qout = 2 * S * G * D * itemsize
    return {"total_bytes": num_layers * (gather_rw + attn_read + qout)}


__all__ = ["paged_decode_attention", "resolve_impl", "paged_gather",
           "sentinel_write_coords", "kernel_hbm_bytes",
           "gather_hbm_bytes", "trace_records", "reset_trace_records",
           "FLAGSHIP_DECODE"]
