"""Row-sharded embedding lookup — the TPU-native parameter server.

The reference keeps sparse variables on parameter-server processes, pulls
rows over gRPC for the forward pass, and pushes `IndexedSlices` gradients
into `SparseConditionalAccumulator`s (reference: graph_transform_lib.py
:330-582, :1041-1211).  On TPU the table lives row-sharded across the
``'shard'`` mesh axis and the pull/push become ICI collectives:

  forward:  all_gather(ids over 'shard')      — ship indices (tiny, int32)
            masked local gather               — each shard reads rows it owns
            psum_scatter(rows over 'shard')   — ship only the looked-up rows
                                                back to the requesting shard
  backward: (transpose, derived by AD)
            all_gather(row grads over 'shard')— ship only touched-row grads
            masked scatter-add                — each shard accumulates into
                                                rows it owns; psum over
                                                'repl' merges replica groups

Bytes on wire per step are O(batch · dim), never O(vocab · dim) — the same
win the reference's PS path has over dense AllReduce, which is the
"sparse-grad bytes on wire" north-star metric (BASELINE.json).

``average_duplicates=True`` reproduces the reference fork's
``SPARSE_AVERAGE_BY_COUNTER`` semantics (graph_transform_lib.py:101-102,
:385-390): duplicate row updates across the *global* batch are averaged by
occurrence count instead of summed, implemented as a custom VJP that
divides the accumulated row gradient by the global row count.

``local_aggregation=True`` (the scope default) is the reference's
two-stage sparse combine (graph_transform_lib.py:1372-1556) re-expressed
for SPMD: each device segment-sums its duplicate ids into unique slots
(stage 1, on-chip, no wire) and only the unique ids/rows/grads cross the
shard axis (stage 2). The static slot capacity min(local ids, vocab+1)
(the +1 slot absorbs out-of-range sentinels) makes the compression
exact — see ``_dedup_capacity``.

``dedup_capacity`` (PSConfig knob) declares a smaller slot count for
workloads the automatic bound can't compress (vocab > per-device ids
but Zipf-heavy duplication). Never lossy: the lookup counts distinct
ids at runtime and any step that overflows the declared capacity on any
device takes a mesh-uniform `lax.cond` fallback to the exact
uncompressed exchange (full wire cost for that step, no dropped
updates).
"""

from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from parallax_tpu.core.mesh import AXIS_REPL, AXIS_SHARD, num_devices


class SliceCapture:
    """Per-trace state for the engine's "slices" sparse-gradient mode.

    The TPU-native IndexedSlices: instead of letting AD scatter row
    cotangents into a dense [V, D] zero array (materialized in HBM every
    step), each registered table's lookup runs on ``stop_gradient(table)``
    and adds a caller-supplied zero ``delta`` of the *rows* shape; the
    gradient w.r.t. that delta IS the per-occurrence row-gradient slice,
    and the captured ids name the rows. The engine pairs (ids, d_delta)
    and applies them with a scatter-only SliceUpdater
    (ops/sparse_optim.py) — the reference's IndexedSlices →
    SparseApplyAdagrad pipeline (language_model_graph.py:48-58,
    graph_transform_lib.py:71-77) with the dense cotangent deleted.

    Used in two passes: discovery (``deltas=None``, under
    ``jax.eval_shape``) records each lookup event's delta shape; the real
    trace feeds matching zero deltas and captures the traced ids.
    """

    def __init__(self, table_paths, deltas=None):
        # id(traced table leaf) -> param path; valid for one trace only
        self.table_paths = dict(table_paths)
        self.deltas = list(deltas) if deltas is not None else None
        self.events = []   # discovery: (path, rows_shape, rows_dtype)
        self.captured = []  # real pass: (path, traced ids array)
        self._next = 0

    def path_of(self, table) -> Optional[str]:
        return self.table_paths.get(id(table))

    def attach(self, path, ids, rows):
        """Record this lookup event; in the real pass add its delta."""
        if self.deltas is None:
            self.events.append((path, tuple(rows.shape),
                                jnp.result_type(rows)))
            return rows
        self.captured.append((path, ids))
        delta = self.deltas[self._next]
        self._next += 1
        if tuple(delta.shape) != tuple(rows.shape):
            raise ValueError(
                f"slices-mode delta {self._next - 1} for {path!r} has "
                f"shape {delta.shape}, lookup produced {rows.shape}; "
                f"lookup order must be deterministic across traces")
        return rows + delta.astype(rows.dtype)


@dataclasses.dataclass(frozen=True)
class _MeshCtx:
    mesh: Mesh
    sharded_shapes: frozenset  # shapes (tuples) of row-sharded tables
    average_duplicates: bool
    # Two-stage sparse combine (reference local_aggregation,
    # graph_transform_lib.py:1372-1556): segment-sum duplicate ids on the
    # owning device BEFORE the cross-shard exchange, so only unique rows
    # cross the wire. Exactness is kept by a static capacity
    # U = min(ids, vocab+1) — never fewer slots than possible distinct
    # values (the +1 absorbs out-of-range sentinels).
    local_aggregation: bool = True
    # User-declared capacity (PSConfig.dedup_capacity) for workloads the
    # automatic bound can't compress (vocab > per-device ids but batches
    # Zipf-heavy). Steps where any device's distinct-id count exceeds it
    # fall back to the exact uncompressed exchange via a mesh-uniform
    # lax.cond — declared capacity is a wire-size target, never a
    # correctness risk.
    # int, or a dict keyed by parameter path / table-shape tuple
    # (PSConfig.dedup_capacity contract)
    dedup_capacity_hint: Union[int, Dict[Any, int], None] = None
    # Cross-replica table-grad combine: None = auto by bytes, True/False
    # forces sparse (gather deduped rows over the whole mesh) vs dense
    # ([rows/shard, dim] psum over 'repl') — see _choose_sparse_repl.
    cross_replica_sparse_hint: Optional[bool] = None
    # trace-time record of sharded lookups: list of (table_shape,
    # effective ids crossing the wire, count-values crossing the wire),
    # one entry per lookup event in the trace — feeds the exact
    # bytes-on-wire accounting
    records: Optional[list] = None
    # "slices" sparse-gradient mode (see SliceCapture)
    slice_capture: Optional[SliceCapture] = None


_CTX: contextvars.ContextVar[Optional[_MeshCtx]] = contextvars.ContextVar(
    "parallax_embedding_mesh_ctx", default=None)


@contextlib.contextmanager
def sharded_lookup_scope(mesh: Mesh, sharded_shapes,
                         average_duplicates: bool = False,
                         records: Optional[list] = None,
                         local_aggregation: bool = True,
                         slice_capture: Optional[SliceCapture] = None,
                         dedup_capacity: Union[int, Dict[Any, int],
                                               None] = None,
                         cross_replica_sparse: Optional[bool] = None):
    """Engine-installed scope: inside it, ``embedding_lookup`` of a table
    whose shape is registered routes through the sharded collective path."""
    token = _CTX.set(_MeshCtx(mesh, frozenset(tuple(s) for s in
                                              sharded_shapes),
                              average_duplicates, local_aggregation,
                              dedup_capacity, cross_replica_sparse,
                              records, slice_capture))
    try:
        yield
    finally:
        _CTX.reset(token)


def current_mesh() -> Optional[Mesh]:
    """The mesh installed by the engine for the current trace (None when
    tracing outside parallel_run, e.g. single-device reference runs).
    Lets model code reach collectives-aware ops (ring_attention) without
    threading the mesh through every signature."""
    ctx = _CTX.get()
    return ctx.mesh if ctx is not None else None


def pad_vocab(vocab_size: int, multiple: int) -> int:
    """Round vocab up so rows split evenly over shards (XLA wants even
    splits; the reference's fixed_size_partitioner tolerated ragged ones)."""
    return -(-vocab_size // multiple) * multiple


def padded_vocab_for(vocab_size: int, num_partitions: Optional[int]) -> int:
    """Shared padding policy for model configs: pad so the table splits
    evenly over ``num_partitions`` (default: every visible device)."""
    p = num_partitions or jax.device_count()
    return pad_vocab(vocab_size, max(p, 1))


def mask_padded_logits(logits: jax.Array, vocab_size: int) -> jax.Array:
    """-inf the phantom classes introduced by vocab padding so they never
    receive probability mass (last-dim layout [..., padded_vocab])."""
    padded = logits.shape[-1]
    if padded == vocab_size:
        return logits
    mask = jnp.concatenate(
        [jnp.zeros((vocab_size,), logits.dtype),
         jnp.full((padded - vocab_size,), -1e9, logits.dtype)])
    return logits + mask


def embedding_lookup(table: jax.Array, ids: jax.Array,
                     sharded: Optional[bool] = None) -> jax.Array:
    """Look up rows of ``table`` (shape [V, D]) at integer ``ids``.

    Outside a `sharded_lookup_scope` (or for tables not registered as
    sharded) this is a plain gather — the replicated/dense path, equivalent
    to the reference's MPI mode where every replica holds the full variable.
    """
    # the layer's name on every op it emits, forward and backward
    # (obs/xprof.LAYER_SCOPES)
    with jax.named_scope("embedding"):
        return _lookup(table, ids, sharded)


def _lookup(table, ids, sharded):
    ctx = _CTX.get()
    # slices mode: this table's gradient flows through the injected
    # delta, not through AD on the table (see SliceCapture)
    slice_path = None
    if ctx is not None and ctx.slice_capture is not None:
        slice_path = ctx.slice_capture.path_of(table)
        if slice_path is not None:
            table = jax.lax.stop_gradient(table)
    use_sharded = sharded
    if use_sharded is None:
        use_sharded = (ctx is not None
                       and tuple(table.shape) in ctx.sharded_shapes)
    if not use_sharded or ctx is None or ctx.mesh.shape[AXIS_SHARD] == 1:
        rows = jnp.take(table, ids, axis=0)
        if slice_path is not None:
            rows = ctx.slice_capture.attach(slice_path, ids, rows)
        return rows
    cap_hint = ctx.dedup_capacity_hint
    if (isinstance(cap_hint, dict) and slice_path is not None
            and slice_path in cap_hint):
        # per-PARAMETER capacity (slices mode identifies the table by
        # path — shape keys can collide, e.g. emb and softmax_w are
        # both [V, 512] in the flagship)
        cap_hint = cap_hint[slice_path]
    cap, guarded = _dedup_capacity(table.shape, ids.shape, ctx.mesh,
                                   ctx.local_aggregation, cap_hint)
    n = num_devices(ctx.mesh)
    n_dev = int(np.prod(ids.shape)) // n
    cap_eff = cap if cap is not None else n_dev
    # occurrence counts cross the wire only when the dedup stage is
    # active AND averaging (the raw path derives them locally)
    has_counts = ctx.average_duplicates and cap is not None
    # Row-grad cotangents carry the table's dtype (JAX cotangent dtype ==
    # primal dtype), so the bytes model must not assume fp32: a bf16
    # table halves the grad planes while the int32 id/count planes stay
    # 4 bytes — near the crossover that flips the cheaper side.
    elem = jnp.dtype(table.dtype).itemsize
    sparse_repl = _choose_sparse_repl(
        ctx.mesh, table.shape, cap_eff, has_counts,
        ctx.cross_replica_sparse_hint, elem)
    if ctx.records is not None:
        # guarded capacities record the declared (compressed) size; an
        # overflow step pays the raw n_dev cost for that step instead
        n_eff = cap_eff * n
        n_cnt = n_eff if has_counts else 0
        ctx.records.append((tuple(table.shape), n_eff, n_cnt,
                            _cross_replica_bytes(
                                ctx.mesh, table.shape, cap_eff,
                                has_counts, sparse_repl, elem),
                            sparse_repl, elem))
    if ctx.average_duplicates or sparse_repl:
        rows = _sharded_lookup_manual(table, ids, ctx.mesh, cap, guarded,
                                      ctx.average_duplicates, sparse_repl)
    else:
        rows = _sharded_lookup(table, ids, ctx.mesh, cap, guarded)
    if slice_path is not None:
        rows = ctx.slice_capture.attach(slice_path, ids, rows)
    return rows


def _cross_replica_bytes(mesh, table_shape, cap_eff: int, counts: bool,
                         sparse_repl: bool, elem_bytes: int = 4) -> int:
    """Mesh-TOTAL bytes the table-grad combine moves ACROSS the 'repl'
    axis per step (zero when repl == 1; same unit as the mesh-total
    shard-exchange terms in the engine's accounting). Dense: every
    device ring-all-reduces its [rows/shard, dim] shard grad. Sparse:
    every device additionally receives the other (repl-1) rows' deduped
    ids/grads in the full-mesh gather. ``counts`` adds the occurrence-
    count plane (shipped only when the dedup stage is active AND
    averaging — the raw path derives counts locally). ``elem_bytes`` is
    the row-grad element size (the table's dtype — cotangents match the
    primal dtype); id/count planes are always int32."""
    r = mesh.shape[AXIS_REPL]
    if r <= 1:
        return 0
    p = mesh.shape[AXIS_SHARD]
    n = r * p
    V = int(table_shape[0])
    D = int(np.prod(table_shape[1:])) if len(table_shape) > 1 else 1
    if sparse_repl:
        per_slot = D * elem_bytes + 4 + (4 if counts else 0)
        return n * (r - 1) * p * cap_eff * per_slot
    return int(n * 2 * (r - 1) / r * (V // p) * D * elem_bytes)


def _choose_sparse_repl(mesh, table_shape, cap_eff: int, counts: bool,
                        hint: Optional[bool],
                        elem_bytes: int = 4) -> bool:
    """Static choice of the cross-replica combine: gather only deduped
    rows over the whole mesh vs dense psum of the shard grad over
    'repl' (the axis that crosses slices/DCN under the slice-aware
    mesh). Shapes are static, so the cheaper side is known at trace
    time — no runtime switch needed."""
    if mesh.shape[AXIS_REPL] <= 1:
        return False
    if hint is not None:
        return bool(hint)
    return (_cross_replica_bytes(mesh, table_shape, cap_eff, counts,
                                 True, elem_bytes)
            < _cross_replica_bytes(mesh, table_shape, cap_eff, counts,
                                   False, elem_bytes))


def _dedup_capacity(table_shape, ids_shape, mesh,
                    local_aggregation: bool,
                    hint: Union[int, Dict[Any, int], None] = None
                    ) -> Tuple[Optional[int], bool]:
    """(static per-device unique-id slot count or None, guarded) for the
    two-stage combine; None when the combine is off or cannot reduce
    wire bytes.

    Exactness needs capacity >= the number of distinct values a device
    can hold. All out-of-range ids (padding sentinels like -1; ids >= V)
    are first collapsed onto the single sentinel V (which no shard owns,
    so it keeps yielding zero rows / dropped grads exactly like the raw
    masked path), giving at most vocab+1 distinct values — so the bound
    min(local ids, vocab+1) is never lossy, and a strict win whenever
    the table is smaller than the device's id list (duplicates then
    guaranteed, e.g. Zipf-heavy batches over a modest vocab).

    A user ``hint`` (PSConfig.dedup_capacity) may set the capacity BELOW
    that bound — then ``guarded=True`` and the lookup adds a runtime
    distinct-count check that falls back to the exact uncompressed
    exchange on overflow (never lossy, see `_sharded_lookup`). The hint
    may be a dict keyed by table shape tuple (different lookups have
    very different distinct-id profiles: input ids vs labels+candidates)
    — unlisted tables get the automatic bound."""
    if not local_aggregation:
        return None, False
    n_dev = int(np.prod(ids_shape)) // num_devices(mesh)
    bound = min(n_dev, int(table_shape[0]) + 1)
    if isinstance(hint, dict):
        hint = hint.get(tuple(table_shape))
    if hint is not None:
        cap = max(1, min(int(hint), bound))
        if cap >= n_dev:
            return None, False
        return cap, cap < bound
    return (bound, False) if bound < n_dev else (None, False)


def _collapse_out_of_range(flat, vocab):
    """Map every id outside [0, vocab) to the sentinel ``vocab`` so the
    dedup capacity bound holds for arbitrary sentinel values."""
    return jnp.where((flat >= 0) & (flat < vocab), flat, vocab)


# --------------------------------------------------------------------------
# Sum path: plain shard_map; AD transpose gives the scatter-add backward.
# With dedup, the forward expands unique rows via take(inv), whose
# transpose segment-sums duplicate row grads BEFORE the cross-shard
# exchange — the two-stage combine falls out of AD for free.
# --------------------------------------------------------------------------


def _distinct_count_overflows(flat, vocab, cap):
    """Mesh-uniform bool: does ANY device's distinct-id count exceed the
    declared capacity? (psum over both axes so every device — including
    other replica rows, whose backward shares an AXIS_REPL psum — takes
    the same `lax.cond` branch)."""
    s = jnp.sort(_collapse_out_of_range(flat, vocab))
    n_unique = 1 + jnp.sum((s[1:] != s[:-1]).astype(jnp.int32))
    over = (n_unique > cap).astype(jnp.int32)
    over = jax.lax.psum(jax.lax.psum(over, AXIS_SHARD), AXIS_REPL)
    return over > 0


def _overflow_flag(ids, vocab, cap, mesh):
    """Replicated scalar bool: any device's distinct-id count exceeds
    the declared capacity (computed ONCE; the avg custom-VJP threads it
    through its residuals so the backward doesn't re-sort/re-psum)."""
    def local(ids_local):
        return _distinct_count_overflows(ids_local.reshape(-1), vocab,
                                         cap)
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=P((AXIS_REPL, AXIS_SHARD)),
        out_specs=P(),
    )(ids)


def _sharded_lookup(table, ids, mesh, dedup_capacity: Optional[int] = None,
                    guarded: bool = False, over=None):
    p = mesh.shape[AXIS_SHARD]
    V, D = table.shape
    assert V % p == 0, (
        f"vocab {V} not divisible by shard axis {p}; use pad_vocab()")
    rows_per_shard = V // p
    ids_shape = ids.shape
    if guarded and over is None:
        over = _overflow_flag(ids, V, dedup_capacity, mesh)

    def local(table_shard, ids_local, over_local):
        # table_shard: [V/p, D]; ids_local: [B/(r·p), ...]
        flat = ids_local.reshape(-1)

        def exchange(fl):
            ids_all = jax.lax.all_gather(fl, AXIS_SHARD, tiled=True)
            rows = _masked_local_gather(table_shard, ids_all,
                                        rows_per_shard)
            return jax.lax.psum_scatter(rows, AXIS_SHARD,
                                        scatter_dimension=0, tiled=True)

        def raw(_):
            return exchange(flat)

        def dedup(_):
            # stage 1: per-device unique compression (sentinel id V is
            # owned by no shard, so those slots contribute zero rows)
            fl, inv = jnp.unique(_collapse_out_of_range(flat, V),
                                 size=dedup_capacity,
                                 fill_value=V, return_inverse=True)
            out_u = exchange(fl)
            return jnp.take(out_u, inv.reshape(-1), axis=0)

        if dedup_capacity is None:
            out = raw(None)
        elif guarded:
            # user-declared capacity below the exactness bound: overflow
            # steps take the exact raw exchange instead of dropping ids
            out = jax.lax.cond(over_local, raw, dedup, None)
        else:
            out = dedup(None)
        return out.reshape(ids_local.shape + (D,))

    if over is None:
        over = jnp.zeros((), jnp.bool_)  # unused placeholder
    # The guarded-capacity cond mixes a branch whose collectives the
    # replication checker can infer (raw) with one it can't see through
    # (dedup's unique+take), and some jax releases reject the branch
    # pair as "mismatched replication types". The checker is purely
    # static — disabling it for exactly this case changes no numerics;
    # out_specs still declares the true layout.
    check = not (guarded and dedup_capacity is not None)
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(AXIS_SHARD, None), P((AXIS_REPL, AXIS_SHARD)), P()),
        out_specs=P((AXIS_REPL, AXIS_SHARD)),
        check_vma=check,
    )(table, ids.reshape(ids_shape), over)


def _masked_local_gather(table_shard, ids_all, rows_per_shard):
    """Gather rows this shard owns for the gathered global id list; rows
    owned elsewhere contribute zeros (summed away by psum_scatter)."""
    lo = jax.lax.axis_index(AXIS_SHARD) * rows_per_shard
    local_idx = ids_all - lo
    valid = (local_idx >= 0) & (local_idx < rows_per_shard)
    safe = jnp.where(valid, local_idx, 0)
    rows = jnp.take(table_shard, safe, axis=0)
    return jnp.where(valid[:, None], rows, jnp.zeros_like(rows))


# --------------------------------------------------------------------------
# Manual-backward path: custom VJP used when the AD transpose isn't the
# backward we want — average-by-counter (SPARSE_AVERAGE_BY_COUNTER
# parity) and/or the sparse cross-replica combine (gathering only the
# deduped rows over 'repl' instead of a dense [rows/shard, dim] psum).
# --------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6))
def _sharded_lookup_manual(table, ids, mesh, dedup_capacity, guarded,
                           average, sparse_repl):
    return _sharded_lookup(table, ids, mesh, dedup_capacity, guarded)


def _manual_fwd(table, ids, mesh, dedup_capacity, guarded, average,
                sparse_repl):
    # compute the overflow decision ONCE and thread it through the
    # residuals so the backward reuses it (no re-sort / re-psum)
    over = (_overflow_flag(ids, table.shape[0], dedup_capacity, mesh)
            if guarded else jnp.zeros((), jnp.bool_))
    out = _sharded_lookup(table, ids, mesh, dedup_capacity, guarded,
                          over=over)
    return out, (table.shape, ids, over)


def _manual_bwd(mesh, dedup_capacity, guarded, average, sparse_repl,
                res, g):
    (V, D), ids, over = res
    p = mesh.shape[AXIS_SHARD]
    r = mesh.shape[AXIS_REPL]
    rows_per_shard = V // p
    gather_axes = ((AXIS_REPL, AXIS_SHARD) if sparse_repl and r > 1
                   else AXIS_SHARD)

    def local(g_local, ids_local, over_local):
        # g_local: [B/(r·p), ..., D]; ids_local: [B/(r·p), ...]
        g_flat = g_local.reshape(-1, D)
        ids_flat = ids_local.reshape(-1)

        def combine(ids_x, g_x, cnt_x):
            # cnt_x None => raw path: one occurrence per position, no
            # count wire cost. With sparse_repl the gather spans the
            # WHOLE mesh, every device computes the identical global
            # scatter, and no repl psum is needed (that dense psum is
            # exactly the DCN traffic this mode exists to avoid).
            g_all = jax.lax.all_gather(g_x, gather_axes, tiled=True)
            ids_all = jax.lax.all_gather(ids_x, gather_axes, tiled=True)
            cnt_all = (jax.lax.all_gather(cnt_x, gather_axes, tiled=True)
                       if cnt_x is not None else None)
            lo = jax.lax.axis_index(AXIS_SHARD) * rows_per_shard
            local_idx = ids_all - lo
            valid = (local_idx >= 0) & (local_idx < rows_per_shard)
            safe = jnp.where(valid, local_idx, 0)
            contrib = jnp.zeros((rows_per_shard, D), g_all.dtype)
            contrib = contrib.at[safe].add(
                jnp.where(valid[:, None], g_all, jnp.zeros_like(g_all)))
            counts = jnp.zeros((rows_per_shard,), jnp.float32)
            if average:
                if cnt_all is None:
                    counts = counts.at[safe].add(
                        valid.astype(jnp.float32))
                else:
                    counts = counts.at[safe].add(
                        jnp.where(valid, cnt_all,
                                  jnp.zeros_like(cnt_all)))
            if gather_axes == AXIS_SHARD:
                # Merge replica groups *before* dividing: the counter
                # counts every contribution in the global batch
                # (reference accumulates across all workers, then
                # averages once). (Also proves repl-invariance to the
                # vma checker; free when repl == 1.)
                contrib = jax.lax.psum(contrib, AXIS_REPL)
                if average:
                    counts = jax.lax.psum(counts, AXIS_REPL)
            if not average:
                return contrib
            scale = jnp.where(counts > 0,
                              1.0 / jnp.maximum(counts, 1.0), 0.0)
            return contrib * scale[:, None].astype(contrib.dtype)

        def raw(_):
            return combine(ids_flat, g_flat, None)

        def dedup(_):
            # stage 1: segment-sum duplicate row grads (and occurrence
            # counts — SPARSE_AVERAGE_BY_COUNTER averages by occurrence,
            # not by unique id) before anything crosses the wire
            ids_x, inv = jnp.unique(
                _collapse_out_of_range(ids_flat, V),
                size=dedup_capacity, fill_value=V, return_inverse=True)
            g_x = jnp.zeros((dedup_capacity, D), g_flat.dtype
                            ).at[inv.reshape(-1)].add(g_flat)
            cnt_x = (jnp.zeros((dedup_capacity,), jnp.float32
                               ).at[inv.reshape(-1)].add(1.0)
                     if average else None)
            return combine(ids_x, g_x, cnt_x)

        if dedup_capacity is None:
            return raw(None)
        if guarded:
            # the forward's decision, from the residuals: overflow steps
            # take the exact uncompressed combine
            return jax.lax.cond(over_local, raw, dedup, None)
        return dedup(None)

    # sparse_repl output is invariant over 'repl' BY CONSTRUCTION (every
    # device scatters the same full-mesh gather), which the static vma
    # checker can't see — hence check_vma=False on that variant only
    grad_table = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P((AXIS_REPL, AXIS_SHARD)), P((AXIS_REPL, AXIS_SHARD)),
                  P()),
        out_specs=P(AXIS_SHARD, None),
        check_vma=not (sparse_repl and r > 1),
    )(g, ids, over)
    ids_ct = np.zeros(ids.shape, dtype=jax.dtypes.float0)
    return (grad_table, ids_ct)


_sharded_lookup_manual.defvjp(_manual_fwd, _manual_bwd)
