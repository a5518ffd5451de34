"""Row-sparse optimizer updates for large embedding tables.

The reference applies sparse gradients with scatter-only kernels
(`SparseApplyAdagrad` / `ScatterAdd`, reference graph_transform_lib.py
:71-77): only the rows a step touched are read and written, so a 793k-row
table doesn't pay a full [V, D] optimizer pass per step.

Two families live here.

``row_sparse_adagrad`` (an optax transformation): the gradient w.r.t. a
looked-up table arrives as a dense scatter-add cotangent, but only
``max_touched_rows`` of its rows can be nonzero (bounded by the step's
id count — a static quantity). It finds those rows with ``top_k`` on row
activity and updates accumulator and parameters by scatter, which XLA
lowers in place on donated TPU buffers. Adagrad's untouched-row update
is a mathematical no-op (accumulator += 0, step -= 0), so the trajectory
is bit-for-bit the dense one whenever the bound holds. Use per-table
via ``optax.multi_transform``::

    tx = optax.multi_transform(
        {"table": row_sparse_adagrad(0.1, max_touched_rows=4096),
         "rest": optax.adagrad(0.1)},
        param_labels={"emb": "table", ...})

``max_touched_rows`` MUST bound the distinct rows touched per step
(e.g. batch·seq_len ids + num_samples candidates); if it doesn't, the
lowest-activity touched rows are silently skipped that step — choose the
bound from static batch shapes, never guess.

The slice updaters (``SliceAdagrad``, ``SliceAdam``; the engine's
"slices" mode) never see a dense cotangent: they take a step's
(ids, row gradients), combine duplicate ids (``_combine_slices``) into
``uids`` — the distinct ids first, sorted ascending, then the sentinel
``V`` in every slot left over — and ``gsum``, and update those rows.
``_combine_slices`` orders and numbers the ids by sorts alone (the ids
sorted with their slots, a cumulative sum over the places where the
sorted id changes, a sort on the slots to carry each place back to its
slot, a sort that moves the distinct ids ahead of the sentinels) and
sums the rows through those places with one scatter-add.

**Which executor updates a SliceAdagrad table's rows** is read off the
table, with no option: the in-place kernel ``adagrad_rows`` where the
backend is a TPU, the engine's mesh (``table_update_scope``) holds one
device so the table is whole on it, parameter and accumulator are
float32 and the row width is a multiple of 128 lanes; the gather and
two scatters over every slot (``_scatter_rows``) everywhere else: a
[V, 1] bias table, a bf16 table, the CPU, a table sharded over a mesh,
a call outside the engine's scope. Both do the same arithmetic in the
same order. ``trace_records()`` says which one each table got.

**What the kernel relies on:** ``uids[:n_valid]`` is sorted, free of
duplicates and below ``V - V % 8``, and ``gsum[i]`` belongs to
``uids[i]``. It walks those ``n_valid`` slots only — the price of a step
follows its distinct rows, not its slot count — and moves aligned
groups of 8 rows (the (8, 128) HBM tile; Mosaic refuses less), so the
up to 7 untouched neighbours of a touched row are rewritten with the
bits they had, and the at most ``V % 8`` rows of a partial last group
are left to ``_scatter_rows`` on 8 slots.
"""

from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import optax


class RowSparseAdagradState(NamedTuple):
    sum_of_squares: jax.Array
    # per-param count of steps that touched more rows than
    # max_touched_rows (those steps DROP their lowest-activity rows).
    # In-state counter rather than a host print: device->host callbacks
    # don't exist on all TPU runtimes, and state survives checkpoints.
    # NOTE: adding this field changed the opt_state pytree — checkpoints
    # written by the 1-field revision need their opt_state re-initialized
    # (or a zeros overflow_steps grafted in) to restore.
    overflow_steps: jax.Array


def row_sparse_adagrad(learning_rate: float, max_touched_rows: int,
                       eps: float = 1e-7,
                       initial_accumulator_value: float = 0.1
                       ) -> optax.GradientTransformation:
    """Adagrad that reads/writes only the rows with nonzero gradient.

    Matches ``optax.adagrad(learning_rate, initial_accumulator_value,
    eps)`` exactly (same state meaning, same trajectory) for 2-D params
    whose per-step gradient touches at most ``max_touched_rows`` rows.
    """
    lr, K, eps_, init = (learning_rate, int(max_touched_rows), eps,
                         initial_accumulator_value)

    def init_fn(params):
        return RowSparseAdagradState(
            jax.tree.map(lambda p: jnp.full(p.shape, init, p.dtype),
                         params),
            jax.tree.map(lambda p: jnp.zeros((), jnp.int32), params))

    def _update_one(g, acc, ovf):
        if g.ndim != 2:
            raise ValueError(
                f"row_sparse_adagrad expects [rows, dim] params, got "
                f"shape {g.shape}; use optax.adagrad for non-tables")
        k = min(K, g.shape[0])
        row_act = jnp.sum(jnp.abs(g), axis=1)
        if k < g.shape[0]:
            # overflow detection: silent row drops would corrupt
            # training with no signal, and row_act makes it ~free
            n_touched = jnp.sum((row_act > 0).astype(jnp.int32))
            ovf = ovf + (n_touched > k).astype(jnp.int32)
        _, idx = jax.lax.top_k(row_act, k)
        g_rows = jnp.take(g, idx, axis=0)
        acc_rows = jnp.take(acc, idx, axis=0) + g_rows * g_rows
        # exact optax semantics AND op order (scale_by_rss then
        # scale_by_learning_rate), so trajectories match bit-for-bit
        inv = jnp.where(acc_rows > 0, jax.lax.rsqrt(acc_rows + eps_), 0.0)
        u_rows = (inv * g_rows) * jnp.asarray(-lr, g_rows.dtype)
        new_acc = acc.at[idx].set(acc_rows)
        updates = jnp.zeros_like(g).at[idx].set(u_rows)
        return updates, new_acc, ovf

    def update_fn(updates, state, params=None):
        del params
        flat_u, treedef = jax.tree_util.tree_flatten(updates)
        flat_a = treedef.flatten_up_to(state.sum_of_squares)
        flat_o = treedef.flatten_up_to(state.overflow_steps)
        out = [_update_one(g, a, o)
               for g, a, o in zip(flat_u, flat_a, flat_o)]
        new_updates = treedef.unflatten([u for u, _, _ in out])
        new_accs = treedef.unflatten([a for _, a, _ in out])
        new_ovf = treedef.unflatten([o for _, _, o in out])
        return new_updates, RowSparseAdagradState(new_accs, new_ovf)

    return optax.GradientTransformation(init_fn, update_fn)


# ---------------------------------------------------------------------------
# Slice updaters: the engine's "slices" sparse-gradient mode
# (ParallaxConfig.sparse_grad_mode="slices") never materializes a dense
# [V, D] cotangent — the lookup sites capture (ids, d_rows) pairs (the
# exact analogue of TF's IndexedSlices, which is what the reference's
# sparse path applies: language_model_graph.py:48-58 feeds IndexedSlices
# straight into AdagradOptimizer, *outside* the global-norm clip) and a
# SliceUpdater applies them scatter-only.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class SliceAdagrad:
    """Adagrad over gradient slices: ``param[r] -= lr * G_r / sqrt(acc_r)``
    where ``G_r`` is the per-occurrence row gradients summed per row (or
    averaged by occurrence count with ``average=True`` — the fork's
    SPARSE_AVERAGE_BY_COUNTER semantics).

    Matches `optax.adagrad` / `row_sparse_adagrad` exactly on rows that
    were touched; untouched rows are never read or written. The reference
    analogue is `SparseApplyAdagrad` (graph_transform_lib.py:71-77).

    ``grad_scale`` multiplies the incoming slices before the update —
    the reference LM1B scales its embedding IndexedSlices by batch_size
    (language_model_graph.py:48-50); expose the same knob.
    """

    learning_rate: float
    initial_accumulator_value: float = 0.1
    eps: float = 1e-7
    grad_scale: float = 1.0

    def init(self, param: jax.Array) -> jax.Array:
        # fp32 accumulator even for bf16 tables: the sum-of-squares adds
        # tiny g² increments that underflow bf16's 8 mantissa bits (the
        # accumulator would freeze and adagrad degrade to fixed-rate
        # SGD); it never crosses the wire, so fp32 costs only HBM
        return jnp.full(param.shape, self.initial_accumulator_value,
                        jnp.float32)

    def update(self, param: jax.Array, acc: jax.Array, ids: jax.Array,
               drows: jax.Array, average: bool = False):
        """Apply slices (ids [N], drows [N, D]) to (param, acc) [V, D].

        Duplicate ids are combined (sum, or occurrence-mean with
        ``average``) BEFORE squaring into the accumulator — identical to
        what the dense scatter-add cotangent would have produced. Ids
        outside [0, V) are dropped (zero-row parity with the sharded
        lookup's sentinel handling).
        """
        V, D = param.shape
        uids, gsum = _combine_slices(ids, drows, V, jnp.float32, average,
                                     self.grad_scale)
        scope = _TABLE_SCOPE.get()
        executor = _row_executor(param, acc, scope.mesh,
                                 jax.default_backend())
        _TRACE_RECORDS[(scope.path, uids.shape[0], D)] = executor
        rows = self._kernel_rows if executor == "kernel" else \
            self._scatter_rows
        return rows(param, acc, uids, gsum)

    def _kernel_rows(self, param, acc, uids, gsum):
        """The live slots' rows read and written once, in place, by
        ``adagrad_rows``. It moves whole groups of 8 rows, so it takes
        the ids below the last multiple of 8 (a prefix: ``uids`` is
        sorted); the at most V % 8 rows past it go through the scatter
        on 8 slots cut from the lists where the kernel stopped."""
        V, D = param.shape
        v_groups = V - V % _GROUP
        n_valid = jnp.sum(uids < v_groups, dtype=jnp.int32)
        param, acc = adagrad_rows(param, acc, uids, n_valid, gsum,
                                  self.learning_rate, self.eps)
        if v_groups == V:
            return param, acc
        n_tail = min(_GROUP, uids.shape[0])
        start = jnp.minimum(n_valid, uids.shape[0] - n_tail)
        uids = jax.lax.dynamic_slice(uids, (start,), (n_tail,))
        uids = jnp.where(uids >= v_groups, uids, V)
        gsum = jax.lax.dynamic_slice(gsum, (start, 0), (n_tail, D))
        return self._scatter_rows(param, acc, uids, gsum)

    def _scatter_rows(self, param, acc, uids, gsum):
        """Gather, update and scatter every slot of (uids, gsum); the
        sentinel slots (id V) are read as 0 and dropped."""
        # NOTE: deliberately NO unique_indices/indices_are_sorted hints:
        # on a v5e the hinted gather and scatters take 11.5-12.1 ms a
        # table at the LM1B cells' shapes whatever the slot count (a
        # pass over the whole [V, D] table) against 0.65-4.2 ms plain
        # (PERF.md section 6, PR 26: the row path alone, not a cell)
        acc_rows = acc.at[uids, :].get(mode="fill", fill_value=0.0)
        acc_rows = acc_rows + gsum * gsum
        inv_rt = jnp.where(acc_rows > 0,
                           jax.lax.rsqrt(acc_rows + self.eps), 0.0)
        u_rows = (inv_rt * gsum) * jnp.asarray(-self.learning_rate,
                                               gsum.dtype)
        new_acc = acc.at[uids, :].set(acc_rows, mode="drop")
        new_param = param.at[uids, :].add(u_rows.astype(param.dtype),
                                          mode="drop")
        return new_param, new_acc


def collect_overflow_steps(opt_state) -> int:
    """Total row_sparse_adagrad overflow events in an optimizer state.

    Walks any optax state pytree, summing `overflow_steps` from every
    RowSparseAdagradState found. Surfaces the silent-drop signal the
    updater records in-state (device->host prints don't exist on all
    TPU runtimes): a nonzero count means some steps touched more rows
    than max_touched_rows and DROPPED their lowest-activity rows —
    raise the bound. `ParallaxSession.sparse_overflow_steps()` calls
    this on the live state.
    """
    total = 0

    def visit(node):
        nonlocal total
        if isinstance(node, RowSparseAdagradState):
            for leaf in jax.tree.leaves(node.overflow_steps):
                total += int(leaf)
            return
        if isinstance(node, (list, tuple)):
            for c in node:
                visit(c)
        elif isinstance(node, dict):
            for c in node.values():
                visit(c)
        elif hasattr(node, "_fields"):  # other NamedTuples (optax states)
            for c in node:
                visit(c)

    visit(opt_state)
    return total


def _combine_slices(ids, drows, V, dtype, average, grad_scale=1.0):
    """Shared slices preprocessing: flatten, scale, collapse
    out-of-range ids onto the sentinel V, unique + segment-sum (or
    occurrence-mean). Returns (uids [N], gsum [N, D]).

    ``uids`` and ``inv`` (each slot's place among the distinct ids) are
    ``jnp.unique(..., size=N, fill_value=V, return_inverse=True)``'s,
    made by three sorts and one cumulative sum: an id-sized gather or
    scatter walks its indices one at a time on a TPU (0.05-0.09 ms for
    10,752 ids on a v5e, where a sort of them with a payload takes
    0.01; PERF.md section 6, PR 36)."""
    ids = ids.reshape(-1)
    drows = drows.reshape(ids.shape[0], -1).astype(dtype)
    if grad_scale != 1.0:
        drows = drows * jnp.asarray(grad_scale, drows.dtype)
    cap = ids.shape[0]
    key = jnp.where((ids >= 0) & (ids < V), ids, V).astype(jnp.int32)
    # the ids sorted (stably) with the slot each came from
    sids, slot = jax.lax.sort((key, jax.lax.iota(jnp.int32, cap)),
                              num_keys=1)
    first = jnp.concatenate([jnp.ones((1,), bool), sids[1:] != sids[:-1]])
    place = jnp.cumsum(first.astype(jnp.int32)) - 1
    # back to arrival order: inv[slot[i]] = place[i], by a sort on slot
    _, inv = jax.lax.sort((slot, place), num_keys=1)
    # every id's first occurrence ahead of the sentinels, still sorted
    uids = jax.lax.sort(jnp.where(first, sids, V))
    gsum = jnp.zeros((cap, drows.shape[1]), drows.dtype).at[inv].add(drows)
    if average:
        cnt = jnp.zeros((cap,), jnp.float32).at[inv].add(1.0)
        gsum = gsum * jnp.where(
            cnt > 0, 1.0 / jnp.maximum(cnt, 1.0), 0.0
        )[:, None].astype(gsum.dtype)
    return uids, gsum


# ---------------------------------------------------------------------------
# The in-place row update: SliceAdagrad's executor for a table that is
# f32, lane-aligned and whole on one TPU (see the module docstring).
# ---------------------------------------------------------------------------

class _TableScope(NamedTuple):
    path: Optional[str]
    mesh: object            # a jax Mesh, or None: placement unknown


_TABLE_SCOPE: contextvars.ContextVar = contextvars.ContextVar(
    "parallax_table_update_scope", default=_TableScope(None, None))


@contextlib.contextmanager
def table_update_scope(path: str, mesh):
    """Engine-installed around one table's ``update``: the table's
    parameter path (for the executor record) and the mesh the step is
    compiled for (a traced table does not show its placement)."""
    token = _TABLE_SCOPE.set(_TableScope(path, mesh))
    try:
        yield
    finally:
        _TABLE_SCOPE.reset(token)


def _row_executor(param, acc, mesh, backend: str) -> str:
    """'kernel' for a table the in-place kernel can serve, else 'xla'
    (the module docstring's rule)."""
    whole_on_one_tpu = (backend == "tpu" and mesh is not None
                        and mesh.size == 1)
    if (whole_on_one_tpu and param.dtype == jnp.float32
            and acc.dtype == jnp.float32 and param.shape[0] >= _GROUP
            and param.shape[1] % 128 == 0):
        return "kernel"
    return "xla"


# Which executor each table's row update got, noted at trace time like
# ops/pallas_lstm's records: chip_smoke.py and the tests read it. One
# entry a (table, slots, width), so a process's tables bound it.
_TRACE_RECORDS: dict = {}


def trace_records():
    """One ``{"table": path, "rows": slots, "dim": D, "executor":
    "kernel" | "xla"}`` per distinct SliceAdagrad update traced since
    the last reset, in trace order (``table`` is None outside the
    engine's scope)."""
    return [{"table": path, "rows": rows, "dim": dim, "executor": ex}
            for (path, rows, dim), ex in _TRACE_RECORDS.items()]


def reset_trace_records():
    _TRACE_RECORDS.clear()


# HBM tiles f32 as (8, 128): Mosaic refuses a DMA of fewer than 8 rows,
# so the unit of transfer is the aligned group of 8 rows.
_GROUP = 8
# ids a grid step, and so the most groups of 8 rows in VMEM at once, for
# rows of up to 512 lanes (64 / 128 / 256 timed within 2 % of each other
# on a v5e; PERF.md, PR 26); wider rows take fewer, so that the three
# buffers stay at 6 MiB
_BLOCK_ROWS = 128


def _block_rows(dim: int) -> int:
    return max(_GROUP, _BLOCK_ROWS * 512 // max(dim, 512) // _GROUP * _GROUP)


def _adagrad_rows_kernel(uids_ref, nv_ref, g_ref, p_in, a_in, p_out, a_out,
                         pbuf, abuf, gbuf, group_of_slot, sem, *, lr, eps,
                         rows):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    del p_in, a_in                      # aliased to p_out / a_out
    base = pl.program_id(0) * rows
    nv = nv_ref[0]

    @pl.when(base < nv)
    def _():
        gbuf[...] = jnp.zeros_like(gbuf)

        tables = ((p_out, pbuf), (a_out, abuf))

        def rows8(group):
            return pl.ds(pl.multiple_of(group * _GROUP, _GROUP), _GROUP)

        def reads(slot, group):
            return [pltpu.make_async_copy(hbm.at[rows8(group)], buf.at[slot],
                                          sem.at[k])
                    for k, (hbm, buf) in enumerate(tables)]

        def writes(slot, group):
            return [pltpu.make_async_copy(buf.at[slot], hbm.at[rows8(group)],
                                          sem.at[2 + k])
                    for k, (hbm, buf) in enumerate(tables)]

        # one walk over the block's sorted ids on the scalar core: a new
        # group of 8 rows takes the next slot and starts its two reads;
        # the id's gradient row goes to its sublane of that slot
        def start_in(r, carry):
            slot, prev = carry
            i = uids_ref[base + r]
            group = i // _GROUP
            new = group != prev
            slot = slot + new.astype(jnp.int32)

            @pl.when(new)
            def _():
                group_of_slot[slot] = group
                for dma in reads(slot, group):
                    dma.start()
            gbuf[slot, pl.ds(i % _GROUP, 1), :] = g_ref[pl.ds(r, 1), :]
            return slot, group
        last_slot, _ = jax.lax.fori_loop(
            0, jnp.minimum(rows, nv - base), start_in,
            (jnp.int32(-1), jnp.int32(-1)))
        n_slots = last_slot + 1

        def wait_in(s, c):
            for dma in reads(s, jnp.int32(0)):
                dma.wait()
            return c
        jax.lax.fori_loop(0, n_slots, wait_in, 0)

        # SliceAdagrad's arithmetic in its order; a group's untouched
        # rows see g = 0 and keep their bits (acc + 0, param + -0.0)
        g = gbuf[...]
        acc = abuf[...] + g * g
        inv_rt = jnp.where(acc > 0, jax.lax.rsqrt(acc + eps), 0.0)
        abuf[...] = acc
        pbuf[...] = pbuf[...] + (inv_rt * g) * jnp.float32(-lr)

        def start_out(s, c):
            for dma in writes(s, group_of_slot[s]):
                dma.start()
            return c
        jax.lax.fori_loop(0, n_slots, start_out, 0)

        # drained before the next block reads: a group whose ids straddle
        # two blocks is read back with this block's update in it
        def wait_out(s, c):
            for dma in writes(s, jnp.int32(0)):
                dma.wait()
            return c
        jax.lax.fori_loop(0, n_slots, wait_out, 0)


def adagrad_rows(param, acc, uids, n_valid, gsum, lr, eps, *,
                 block_rows=None, interpret=None):
    """Adagrad on the rows ``uids[:n_valid]`` of (param, acc) f32[V, D],
    each row read and written once, in place (the outputs alias the
    inputs). Relies on: ``uids[:n_valid]`` sorted ascending, free of
    duplicates and below ``V - V % 8``; ``gsum[i]`` is row ``uids[i]``'s
    combined gradient. Slots at or past ``n_valid`` cost one empty grid
    step each and no transfer. The rows that share an aligned group of
    8 with a live row are rewritten with the bits they had."""
    # imported where a kernel is traced: `import parallax_tpu` stays
    # free of pallas (0.9 s) for programs that run no kernel
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    D = param.shape[1]
    rows = block_rows or _block_rows(D)

    def g_map(b, uids_ref, nv_ref):
        # dead blocks re-use the last live block: nothing is fetched
        del uids_ref
        last = jnp.maximum((nv_ref[0] + rows - 1) // rows - 1, 0)
        return jnp.minimum(b, last), 0

    hbm = pl.BlockSpec(memory_space=pl.ANY)
    buf = pltpu.VMEM((rows, _GROUP, D), jnp.float32)
    return pl.pallas_call(
        functools.partial(_adagrad_rows_kernel, lr=lr, eps=eps, rows=rows),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(pl.cdiv(uids.shape[0], rows),),
            in_specs=[pl.BlockSpec((rows, D), g_map), hbm, hbm],
            out_specs=[hbm, hbm],
            scratch_shapes=[buf, buf, buf,
                            pltpu.SMEM((rows,), jnp.int32),
                            pltpu.SemaphoreType.DMA((4,))]),
        out_shape=[jax.ShapeDtypeStruct(param.shape, param.dtype),
                   jax.ShapeDtypeStruct(acc.shape, acc.dtype)],
        input_output_aliases={3: 0, 4: 1},
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=48 * 1024 * 1024),
        name="adagrad_rows",
        interpret=interpret,
    )(uids, jnp.reshape(n_valid, (1,)).astype(jnp.int32), gsum, param, acc)


class SliceAdamState(NamedTuple):
    m: jax.Array        # first moment, touched rows only
    v: jax.Array        # second moment, touched rows only
    count: jax.Array    # global step counter (bias correction)


@dataclasses.dataclass(frozen=True)
class SliceAdam:
    """Lazy Adam over gradient slices — TF `LazyAdamOptimizer`
    semantics: moments update ONLY for rows touched this step (untouched
    rows do not decay), bias correction uses the global step count.

    By design this differs from dense `optax.adam` trajectories (dense
    adam decays every row's moments every step, costing a full [V, D]
    pass); it is the standard large-vocab tradeoff. Use via
    `Model.slice_updaters` with `Config(sparse_grad_mode="slices")`.
    """

    learning_rate: float
    b1: float = 0.9
    b2: float = 0.999
    eps: float = 1e-8
    grad_scale: float = 1.0

    def init(self, param: jax.Array) -> SliceAdamState:
        # fp32 moments for the same underflow reason as SliceAdagrad's
        # accumulator (v accumulates (1-b2)·g², far below bf16 epsilon)
        z = jnp.zeros(param.shape, jnp.float32)
        return SliceAdamState(z, z, jnp.zeros((), jnp.int32))

    def update(self, param: jax.Array, state: SliceAdamState,
               ids: jax.Array, drows: jax.Array, average: bool = False):
        V = param.shape[0]
        uids, gsum = _combine_slices(ids, drows, V, jnp.float32, average,
                                     self.grad_scale)
        t = state.count + 1
        m_r = (self.b1 * state.m.at[uids, :].get(mode="fill",
                                                 fill_value=0.0)
               + (1.0 - self.b1) * gsum)
        v_r = (self.b2 * state.v.at[uids, :].get(mode="fill",
                                                 fill_value=0.0)
               + (1.0 - self.b2) * gsum * gsum)
        tf_ = t.astype(jnp.float32)
        m_hat = m_r / (1.0 - jnp.asarray(self.b1, jnp.float32) ** tf_)
        v_hat = v_r / (1.0 - jnp.asarray(self.b2, jnp.float32) ** tf_)
        u_rows = (-self.learning_rate * m_hat
                  / (jnp.sqrt(v_hat) + self.eps))
        # sentinel rows (id == V) have zero gsum; with zero moments their
        # update is exactly 0, and mode="drop" discards them anyway
        new_m = state.m.at[uids, :].set(m_r, mode="drop")
        new_v = state.v.at[uids, :].set(v_r, mode="drop")
        new_param = param.at[uids, :].add(u_rows.astype(param.dtype),
                                          mode="drop")
        return new_param, SliceAdamState(new_m, new_v, t)
