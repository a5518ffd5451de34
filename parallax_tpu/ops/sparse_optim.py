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

**Which executor updates a slice table's rows** is read off the table,
with no option, by one rule for both updaters: the in-place walk where
the backend is a TPU, the engine's mesh (``table_update_scope``) holds
one device so the table is whole on it, parameter and state are float32,
the row width is a multiple of 128 lanes and the table is larger than
the core's VMEM; the gathers and scatters over every slot
(``_scatter_rows``) everywhere else: a [V, 1] bias table, a bf16 table,
the CPU, a table sharded over a mesh, a call outside the engine's
scope, a table the compiler can hold in VMEM. That last: XLA stages
such a table in VMEM for the embedding's gather and for its own
scatters, and the step's other arrays are placed around it; with the
kernel reading the table from HBM the compiler placed them anew, and on
Mellum2's 113 MB table (128 MiB of VMEM on a v5e) a gather of the
experts' backward lost its VMEM: 2.6 % of the step, more than the
kernel saves (PERF.md, PR 40). The walk has two update rules, picked by
the updater that calls it: ``adagrad_rows`` on (param, acc) for
``SliceAdagrad``, ``adam_rows`` on (param, m, v) for ``SliceAdam``'s
lazy Adam. Either executor does the same arithmetic in the same order.
``trace_records()`` says which rule and executor each table got.

**What the walk relies on:** ``uids[:n_valid]`` is sorted, free of
duplicates and below ``V - V % 8``, and ``gsum[i]`` belongs to
``uids[i]``. It walks those ``n_valid`` slots only — the price of a step
follows its distinct rows, not its slot count — and moves aligned
groups of 8 rows (the (8, 128) HBM tile; Mosaic refuses less), so up to
7 untouched neighbours of a touched row are read and written back. Under
Adagrad they see ``g = 0`` and keep their bits. Under lazy Adam they
would not (``m`` would decay to ``b1 * m`` and ``m_hat`` move the row),
so ``adam_rows`` marks which sublanes of a group hold a live id and
writes the others back as they were read: an untouched row keeps
param, m and v bit for bit, as the scatters leave it. The at most
``V % 8`` rows of a partial last group are left to ``_scatter_rows`` on
8 slots.
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
        V = param.shape[0]
        uids, gsum = _combine_slices(ids, drows, V, jnp.float32, average,
                                     self.grad_scale)
        executor = _executor("adagrad", param, acc, uids)
        rows = self._kernel_rows if executor == "kernel" else \
            self._scatter_rows
        return rows(param, acc, uids, gsum)

    def _kernel_rows(self, param, acc, uids, gsum):
        return _in_place(
            lambda *a: adagrad_rows(*a, self.learning_rate, self.eps),
            self._scatter_rows, (param, acc), uids, gsum)

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
# The in-place row update: both slice updaters' executor for a table that
# is f32, lane-aligned and whole on one TPU (see the module docstring).
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


def _vmem_bytes() -> int:
    """The VMEM of the TPU core that runs the step."""
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.get_tpu_info().vmem_capacity_bytes


def _row_executor(param, acc, mesh, backend: str) -> str:
    """'kernel' for a table the in-place kernel can serve, else 'xla'
    (the module docstring's rule)."""
    whole_on_one_tpu = (backend == "tpu" and mesh is not None
                        and mesh.size == 1)
    if (whole_on_one_tpu and param.dtype == jnp.float32
            and acc.dtype == jnp.float32 and param.shape[0] >= _GROUP
            and param.shape[1] % 128 == 0
            and param.shape[0] * param.shape[1] * 4 > _vmem_bytes()):
        return "kernel"
    return "xla"


# Which rule and executor each table's row update got, noted at trace
# time like ops/pallas_lstm's records: chip_smoke.py and the tests read
# it. One entry a (table, slots, width, rule), so a process's tables
# bound it.
_TRACE_RECORDS: dict = {}


def _executor(rule, param, acc, uids) -> str:
    """The executor ``_row_executor`` picks for this table in the
    engine's scope, noted under the updater's ``rule``."""
    scope = _TABLE_SCOPE.get()
    executor = _row_executor(param, acc, scope.mesh, jax.default_backend())
    _TRACE_RECORDS[(scope.path, uids.shape[0], param.shape[1], rule)] = \
        executor
    return executor


def trace_records():
    """One ``{"table": path, "rows": slots, "dim": D, "rule": "adagrad" |
    "adam", "executor": "kernel" | "xla"}`` per distinct slice update
    traced since the last reset, in trace order (``table`` is None
    outside the engine's scope)."""
    return [{"table": path, "rows": rows, "dim": dim, "rule": rule,
             "executor": ex}
            for (path, rows, dim, rule), ex in _TRACE_RECORDS.items()]


def reset_trace_records():
    _TRACE_RECORDS.clear()


def _in_place(kernel, scatter, tables, uids, gsum):
    """The live slots' rows of ``tables`` read and written once, in
    place, by ``kernel(*tables, uids, n_valid, gsum)``. It moves whole
    groups of 8 rows, so it takes the ids below the last multiple of 8
    (a prefix: ``uids`` is sorted); the at most V % 8 rows past it go
    through ``scatter(*tables, uids, gsum)`` on 8 slots cut from the
    lists where the kernel stopped."""
    V, D = tables[0].shape
    v_groups = V - V % _GROUP
    n_valid = jnp.sum(uids < v_groups, dtype=jnp.int32)
    tables = tuple(kernel(*tables, uids, n_valid, gsum))
    if v_groups == V:
        return tables
    n_tail = min(_GROUP, uids.shape[0])
    start = jnp.minimum(n_valid, uids.shape[0] - n_tail)
    uids = jax.lax.dynamic_slice(uids, (start,), (n_tail,))
    uids = jnp.where(uids >= v_groups, uids, V)
    gsum = jax.lax.dynamic_slice(gsum, (start, 0), (n_tail, D))
    return scatter(*tables, uids, gsum)


# HBM tiles f32 as (8, 128): Mosaic refuses a DMA of fewer than 8 rows,
# so the unit of transfer is the aligned group of 8 rows.
_GROUP = 8
# ids a grid step, and so the most groups of 8 rows in VMEM at once, for
# rows of up to 512 lanes (64 / 128 / 256 timed within 2 % of each other
# on a v5e; PERF.md, PR 26); wider rows take fewer, so that the three
# buffers stay at 6 MiB
_BLOCK_ROWS = 128
# lazy Adam's four buffers (param, m, v, g) at most this many bytes: 32
# ids a grid step at 3,840 lanes, 64 at 2,048 (blocks of 16 to 64 ids
# within 20 % of each other on a v5e; PERF.md, PR 40)
_ADAM_BLOCK_BYTES = 16 * 1024 * 1024


def _block_rows(dim: int) -> int:
    return max(_GROUP, _BLOCK_ROWS * 512 // max(dim, 512) // _GROUP * _GROUP)


def _adam_block_rows(dim: int) -> int:
    per_row = 4 * _GROUP * dim * 4
    return max(_GROUP, min(_BLOCK_ROWS, _ADAM_BLOCK_BYTES // per_row)
               // _GROUP * _GROUP)


def _group_copies(tables, sem):
    """``copies(slot, group, out)``: the DMAs of one slot's aligned group
    of 8 rows between each (HBM table, VMEM buffer) pair of ``tables``,
    into the buffers or (``out``) back; ``sem(k, slot, out)`` is the
    semaphore of table k's copy."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def rows8(group):
        return pl.ds(pl.multiple_of(group * _GROUP, _GROUP), _GROUP)

    def copies(slot, group, out):
        return [pltpu.make_async_copy(buf.at[slot], hbm.at[rows8(group)],
                                      sem(k, slot, out)) if out else
                pltpu.make_async_copy(hbm.at[rows8(group)], buf.at[slot],
                                      sem(k, slot, out))
                for k, (hbm, buf) in enumerate(tables)]
    return copies


def _walk(uids_ref, g_ref, gbuf, group_of_slot, base, count, copies,
          live_of_slot=None):
    """The walk both rules share, over a block's ``count`` sorted ids on
    the scalar core: a new group of 8 rows takes the next slot and
    starts its reads; the id's gradient row goes to its sublane of that
    slot and, where ``live_of_slot`` is given, the sublane's bit into
    the slot's mask. Returns the number of slots taken."""
    from jax.experimental import pallas as pl

    def start_in(r, carry):
        slot, prev = carry
        i = uids_ref[base + r]
        group = i // _GROUP
        new = group != prev
        slot = slot + new.astype(jnp.int32)

        @pl.when(new)
        def _():
            group_of_slot[slot] = group
            for dma in copies(slot, group, False):
                dma.start()
        gbuf[slot, pl.ds(i % _GROUP, 1), :] = g_ref[pl.ds(r, 1), :]
        if live_of_slot is not None:
            was = jnp.where(new, 0, live_of_slot[slot])
            live_of_slot[slot] = was | (1 << (i % _GROUP))
        return slot, group
    last_slot, _ = jax.lax.fori_loop(
        0, count, start_in, (jnp.int32(-1), jnp.int32(-1)))
    return last_slot + 1


def _drain(n_slots, copies):
    """Every write-back waited for before the next block reads: a group
    whose ids straddle two blocks is read back with this block's update
    in it."""
    def wait_out(s, c):
        for dma in copies(s, jnp.int32(0), True):
            dma.wait()
        return c
    jax.lax.fori_loop(0, n_slots, wait_out, 0)


def _adagrad_rows_kernel(uids_ref, nv_ref, g_ref, p_in, a_in, p_out, a_out,
                         pbuf, abuf, gbuf, group_of_slot, sem, *, lr, eps,
                         rows):
    from jax.experimental import pallas as pl

    del p_in, a_in                      # aliased to p_out / a_out
    base = pl.program_id(0) * rows
    nv = nv_ref[0]

    @pl.when(base < nv)
    def _():
        gbuf[...] = jnp.zeros_like(gbuf)
        copies = _group_copies(((p_out, pbuf), (a_out, abuf)),
                               lambda k, slot, out: sem.at[2 * out + k])
        n_slots = _walk(uids_ref, g_ref, gbuf, group_of_slot, base,
                        jnp.minimum(rows, nv - base), copies)

        def wait_in(s, c):
            for dma in copies(s, jnp.int32(0), False):
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
            for dma in copies(s, group_of_slot[s], True):
                dma.start()
            return c
        jax.lax.fori_loop(0, n_slots, start_out, 0)
        _drain(n_slots, copies)


def _adam_rows_kernel(uids_ref, nv_ref, corr_ref, g_ref, p_in, m_in, v_in,
                      p_out, m_out, v_out, pbuf, mbuf, vbuf, gbuf,
                      group_of_slot, live_of_slot, sem_in, sem_out, *, lr,
                      b1, b2, eps, rows):
    from jax.experimental import pallas as pl

    del p_in, m_in, v_in                # aliased to the outputs
    base = pl.program_id(0) * rows
    nv = nv_ref[0]

    @pl.when(base < nv)
    def _():
        # a slot's reads signal semaphores of its own, so that it is
        # updated and written back as soon as its rows are in, while the
        # later slots' reads are still on their way
        copies = _group_copies(
            ((p_out, pbuf), (m_out, mbuf), (v_out, vbuf)),
            lambda k, slot, out: sem_out.at[k] if out else sem_in.at[k, slot])
        n_slots = _walk(uids_ref, g_ref, gbuf, group_of_slot, base,
                        jnp.minimum(rows, nv - base), copies, live_of_slot)
        sublane = jax.lax.broadcasted_iota(jnp.int32, gbuf.shape[1:], 0)
        c1, c2 = corr_ref[0], corr_ref[1]

        def update(s, c):
            for dma in copies(s, jnp.int32(0), False):
                dma.wait()
            # SliceAdam's arithmetic in its order, kept on the sublanes
            # that hold a live id: an untouched neighbour keeps param, m
            # and v bit for bit (lazy Adam: its moments do not decay)
            live = ((live_of_slot[s] >> sublane) & 1) == 1
            g, p, m, v = gbuf[s], pbuf[s], mbuf[s], vbuf[s]
            m_r = b1 * m + (1.0 - b1) * g
            v_r = b2 * v + (1.0 - b2) * g * g
            m_hat = m_r / c1
            v_hat = v_r / c2
            u = -lr * m_hat / (jnp.sqrt(v_hat) + eps)
            pbuf[s] = jnp.where(live, p + u, p)
            mbuf[s] = jnp.where(live, m_r, m)
            vbuf[s] = jnp.where(live, v_r, v)
            for dma in copies(s, group_of_slot[s], True):
                dma.start()
            return c
        jax.lax.fori_loop(0, n_slots, update, 0)
        _drain(n_slots, copies)


def _rows_call(kernel, name, tables, uids, n_valid, gsum, scalars, rows,
               scratch, interpret):
    """The pallas_call both rules share: the ids and ``n_valid`` (and the
    rule's ``scalars``) scalar-prefetched, ``gsum`` in blocks of ``rows``,
    the f32[V, D] ``tables`` left in HBM and aliased to the outputs, a
    VMEM buffer of ``rows`` groups of 8 rows for each table and for the
    gradients, the slots' groups in SMEM, then the rule's ``scratch``."""
    # imported where a kernel is traced: `import parallax_tpu` stays
    # free of pallas (0.9 s) for programs that run no kernel
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    D = tables[0].shape[1]
    n_prefetch = 2 + len(scalars)

    def g_map(b, uids_ref, nv_ref, *_):
        # dead blocks re-use the last live block: nothing is fetched
        del uids_ref
        last = jnp.maximum((nv_ref[0] + rows - 1) // rows - 1, 0)
        return jnp.minimum(b, last), 0

    hbm = pl.BlockSpec(memory_space=pl.ANY)
    buf = pltpu.VMEM((rows, _GROUP, D), jnp.float32)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=n_prefetch,
            grid=(pl.cdiv(uids.shape[0], rows),),
            in_specs=[pl.BlockSpec((rows, D), g_map)] + [hbm] * len(tables),
            out_specs=[hbm] * len(tables),
            scratch_shapes=[buf] * (len(tables) + 1)
            + [pltpu.SMEM((rows,), jnp.int32)] + scratch),
        out_shape=[jax.ShapeDtypeStruct(t.shape, t.dtype) for t in tables],
        input_output_aliases={n_prefetch + 1 + k: k
                              for k in range(len(tables))},
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=48 * 1024 * 1024),
        name=name,
        interpret=interpret,
    )(uids, jnp.reshape(n_valid, (1,)).astype(jnp.int32), *scalars, gsum,
      *tables)


def adagrad_rows(param, acc, uids, n_valid, gsum, lr, eps, *,
                 block_rows=None, interpret=None):
    """Adagrad on the rows ``uids[:n_valid]`` of (param, acc) f32[V, D],
    each row read and written once, in place (the outputs alias the
    inputs). Relies on: ``uids[:n_valid]`` sorted ascending, free of
    duplicates and below ``V - V % 8``; ``gsum[i]`` is row ``uids[i]``'s
    combined gradient. Slots at or past ``n_valid`` cost one empty grid
    step each and no transfer. The rows that share an aligned group of
    8 with a live row are rewritten with the bits they had."""
    from jax.experimental.pallas import tpu as pltpu

    rows = block_rows or _block_rows(param.shape[1])
    return _rows_call(
        functools.partial(_adagrad_rows_kernel, lr=lr, eps=eps, rows=rows),
        "adagrad_rows", (param, acc), uids, n_valid, gsum, (), rows,
        [pltpu.SemaphoreType.DMA((4,))], interpret)


def adam_rows(param, m, v, uids, n_valid, gsum, corr, lr, b1, b2, eps, *,
              block_rows=None, interpret=None):
    """Lazy Adam on the rows ``uids[:n_valid]`` of (param, m, v)
    f32[V, D] by the walk of ``adagrad_rows``, under the same contract;
    ``corr`` f32[2] holds the bias corrections ``1 - b1**t``,
    ``1 - b2**t``. A slot updates only the sublanes its ids named and
    writes the rest of its group of 8 back as it was read."""
    from jax.experimental.pallas import tpu as pltpu

    rows = block_rows or _adam_block_rows(param.shape[1])
    return _rows_call(
        functools.partial(_adam_rows_kernel, lr=lr, b1=b1, b2=b2, eps=eps,
                          rows=rows),
        "adam_rows", (param, m, v), uids, n_valid, gsum,
        (corr.astype(jnp.float32),), rows,
        [pltpu.SMEM((rows,), jnp.int32), pltpu.SemaphoreType.DMA((3, rows)),
         pltpu.SemaphoreType.DMA((3,))], interpret)


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

    The rows are updated by the executor rule of ``SliceAdagrad``
    (``_row_executor``): ``adam_rows``, the in-place walk of aligned
    groups of 8 rows, on a float32 lane-aligned table whole on one TPU
    and larger than its VMEM; the gathers and scatters over every slot
    elsewhere. The walk marks
    which sublanes of a group hold a live id and writes the others back
    as they were read: where Adagrad's ``g = 0`` leaves a row's bits
    alone, Adam's would decay its ``m`` and move it by ``m_hat``.
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
        tf_ = t.astype(jnp.float32)
        # the bias corrections of the global count, handed to either
        # executor as values: no compiled program depends on the step
        corr = jnp.stack([1.0 - jnp.asarray(self.b1, jnp.float32) ** tf_,
                          1.0 - jnp.asarray(self.b2, jnp.float32) ** tf_])
        # init makes m and v alike: m stands for both
        executor = _executor("adam", param, state.m, uids)
        rows = self._kernel_rows if executor == "kernel" else \
            self._scatter_rows
        param, m, v = rows(param, state.m, state.v, uids, gsum, corr)
        return param, SliceAdamState(m, v, t)

    def _kernel_rows(self, param, m, v, uids, gsum, corr):
        return _in_place(
            lambda *a: adam_rows(*a, corr, self.learning_rate, self.b1,
                                 self.b2, self.eps),
            functools.partial(self._scatter_rows, corr=corr),
            (param, m, v), uids, gsum)

    def _scatter_rows(self, param, m, v, uids, gsum, corr):
        """Gather, update and scatter every slot of (uids, gsum); the
        sentinel slots (id V) are read as 0 and dropped."""
        m_r = (self.b1 * m.at[uids, :].get(mode="fill", fill_value=0.0)
               + (1.0 - self.b1) * gsum)
        v_r = (self.b2 * v.at[uids, :].get(mode="fill", fill_value=0.0)
               + (1.0 - self.b2) * gsum * gsum)
        m_hat = m_r / corr[0]
        v_hat = v_r / corr[1]
        u_rows = (-self.learning_rate * m_hat
                  / (jnp.sqrt(v_hat) + self.eps))
        # sentinel rows (id == V) have zero gsum; with zero moments their
        # update is exactly 0, and mode="drop" discards them anyway
        new_m = m.at[uids, :].set(m_r, mode="drop")
        new_v = v.at[uids, :].set(v_r, mode="drop")
        new_param = param.at[uids, :].add(u_rows.astype(param.dtype),
                                          mode="drop")
        return new_param, new_m, new_v
