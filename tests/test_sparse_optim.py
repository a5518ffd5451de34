"""Row-sparse optimizer updates (ops/sparse_optim.py) — scatter-only
adagrad parity with the reference's SparseApplyAdagrad semantics
(reference graph_transform_lib.py:71-77)."""

import collections
import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from parallax_tpu.ops import sparse_optim as so
from parallax_tpu.ops.sparse_optim import (collect_overflow_steps,
                                           row_sparse_adagrad)

V, D, K = 64, 8, 12


def _sparse_grad(rng, n_rows):
    g = np.zeros((V, D), np.float32)
    rows = rng.choice(V, size=n_rows, replace=False)
    g[rows] = rng.standard_normal((n_rows, D))
    return jnp.asarray(g)


def test_trajectory_matches_dense_adagrad(rng):
    lr = 0.3
    dense = optax.adagrad(lr, initial_accumulator_value=0.1)
    sparse = row_sparse_adagrad(lr, max_touched_rows=K,
                                initial_accumulator_value=0.1)
    p_d = p_s = jnp.asarray(rng.standard_normal((V, D)).astype(np.float32))
    s_d, s_s = dense.init(p_d), sparse.init(p_s)
    for step in range(10):
        g = _sparse_grad(rng, n_rows=min(K, 3 + step))
        u_d, s_d = dense.update(g, s_d, p_d)
        u_s, s_s = sparse.update(g, s_s, p_s)
        p_d = optax.apply_updates(p_d, u_d)
        p_s = optax.apply_updates(p_s, u_s)
        np.testing.assert_array_equal(np.asarray(p_s), np.asarray(p_d))
    np.testing.assert_array_equal(np.asarray(s_s.sum_of_squares),
                                  np.asarray(s_d[0].sum_of_squares))


def test_update_cost_is_lower():
    """The scatter-only update does a small fraction of the dense
    adagrad's FLOPs on a large table (the reference's win from
    SparseApplyAdagrad vs dense ApplyAdagrad)."""
    big_v, big_d, k = 16384, 256, 256
    lr = 0.1

    def run(tx):
        def step(p, s, g):
            u, s = tx.update(g, s, p)
            return optax.apply_updates(p, u), s
        p = jnp.zeros((big_v, big_d))
        s = tx.init(p)
        c = jax.jit(step, donate_argnums=(0, 1)).lower(
            p, s, jnp.zeros((big_v, big_d))).compile()
        return c.cost_analysis()["flops"]

    dense_flops = run(optax.adagrad(lr))
    sparse_flops = run(row_sparse_adagrad(lr, max_touched_rows=k))
    assert sparse_flops < dense_flops / 2, (sparse_flops, dense_flops)


def test_overflow_steps_counted_and_collectable(rng):
    """Touching more rows than the bound must be visible: the state
    counts the overflow and collect_overflow_steps surfaces it from an
    arbitrarily nested optax state (silent drops corrupt training)."""
    sparse = row_sparse_adagrad(0.1, max_touched_rows=K)
    # nest inside chain + multi_transform like real model wiring
    tx = optax.chain(optax.clip_by_global_norm(1e9), sparse)
    p = jnp.asarray(rng.standard_normal((V, D)).astype(np.float32))
    st = tx.init(p)
    assert collect_overflow_steps(st) == 0
    g_ok = _sparse_grad(rng, n_rows=K)
    _, st = tx.update(g_ok, st, p)
    assert collect_overflow_steps(st) == 0
    g_over = _sparse_grad(rng, n_rows=K + 5)
    _, st = tx.update(g_over, st, p)
    _, st = tx.update(g_over, st, p)
    assert collect_overflow_steps(st) == 2


def test_rejects_non_table_params():
    tx = row_sparse_adagrad(0.1, max_touched_rows=4)
    p = jnp.zeros((8,))
    s = tx.init(p)
    with pytest.raises(ValueError, match="rows, dim"):
        tx.update(jnp.zeros((8,)), s, p)


@pytest.mark.slow
def test_lm1b_wiring_trajectory_unchanged(rng):
    """LM1BConfig.max_touched_rows routes tables to the scatter path with
    an unchanged training trajectory."""
    import parallax_tpu as parallax
    from parallax_tpu.models import lm1b

    batches = [lm1b.make_batch(rng, 8, 4, 1000) for _ in range(3)]

    def run(max_rows):
        cfg = lm1b.tiny_config(num_partitions=8,
                               max_touched_rows=max_rows)
        sess, *_ = parallax.parallel_run(
            lm1b.build_model(cfg),
            parallax_config=parallax.Config(run_option="HYBRID",
                                            search_partitions=False))
        losses = [float(sess.run("loss", feed_dict=b)) for b in batches]
        emb = np.asarray(sess.state.params["emb"])
        sess.close()
        return losses, emb

    # emb touches <= 8*4 rows, softmax_w <= 64 samples + 32 labels
    losses_sparse, emb_sparse = run(128)
    losses_dense, emb_dense = run(None)
    np.testing.assert_allclose(losses_sparse, losses_dense, rtol=1e-5)
    np.testing.assert_allclose(emb_sparse, emb_dense, rtol=1e-5,
                               atol=1e-7)


# ---------------------------------------------------------------------------
# The in-place row walk under both rules: SliceAdagrad's `adagrad_rows`
# and SliceAdam's `adam_rows` (interpret mode here; Adagrad's compiled
# by Mosaic and compared at the cells' shapes in chip_smoke.py's kernels
# phase, Adam's in every Adam cell's run). The test steers the choice,
# the program has no option for it.
# ---------------------------------------------------------------------------

KD = 128          # one lane tile: the narrowest table the kernel takes


def _through_kernel(monkeypatch):
    """Route the updaters through the kernels (interpreted off the
    chip), in blocks of 16 ids so that a few dozen ids span blocks."""
    monkeypatch.setattr(so, "_row_executor", lambda *a: "kernel")
    for kernel in ("adagrad_rows", "adam_rows"):
        monkeypatch.setattr(so, kernel, functools.partial(
            getattr(so, kernel), block_rows=16))


def _ids_duplicates(rng, V, cap):
    return rng.choice(V // 4, size=cap)


def _ids_out_of_range(rng, V, cap):
    ids = rng.choice(V, size=cap)
    ids[::5] = -1
    ids[1::7] = V
    return ids


def _ids_none_live(rng, V, cap):
    return np.where(np.arange(cap) % 2 == 0, -1, V)


def _ids_all_live(rng, V, cap):
    return rng.choice(V - V % 8, size=cap, replace=False)


def _ids_split_group(rng, V, cap):
    # consecutive ids from 4 on: with 16 ids a block, rows 16-19 close
    # block 0 and rows 20-23 of the same group of 8 open block 1
    return 4 + np.arange(cap)


def _ids_last_partial_group(rng, V, cap):
    ids = rng.choice(V, size=cap)
    ids[:3] = [V - 1, V - 2, V - V % 8 - 1]
    return ids


ROW_KERNEL_CASES = {
    # name: (V, slots, ids, average)
    "duplicates": (1000, 96, _ids_duplicates, False),
    "out_of_range_ids": (1000, 96, _ids_out_of_range, False),
    "no_live_row": (1000, 32, _ids_none_live, False),
    "every_slot_live": (1000, 64, _ids_all_live, False),
    "slots_not_a_multiple_of_the_block": (1000, 41, _ids_duplicates, False),
    "group_split_between_blocks": (1000, 80, _ids_split_group, False),
    "last_partial_group": (1006, 96, _ids_last_partial_group, False),
    "partial_group_fewer_than_8_slots": (1006, 5,
                                         _ids_last_partial_group, False),
    "average": (1000, 96, _ids_duplicates, True),
}


def _table(rng, V):
    p = jnp.asarray(rng.standard_normal((V, KD)).astype(np.float32))
    a = jnp.asarray(rng.uniform(0.1, 2.0, (V, KD)).astype(np.float32))
    return p, a


def _adam_state(rng, V):
    """Moments an untouched row would visibly decay, at a count past 1."""
    return so.SliceAdamState(
        jnp.asarray(0.1 * rng.standard_normal((V, KD)).astype(np.float32)),
        jnp.asarray(rng.uniform(0.01, 1.0, (V, KD)).astype(np.float32)),
        jnp.int32(2))


# rule: (updater, its state on a table of V rows, steps: Adam's several,
# so that the bias corrections run at t > 1)
RULES = {
    "adagrad": (lambda: so.SliceAdagrad(0.2), lambda rng, V: _table(rng, V)[1],
                1),
    "adam": (lambda: so.SliceAdam(0.05), _adam_state, 3),
}


@pytest.mark.parametrize("case", sorted(ROW_KERNEL_CASES))
@pytest.mark.parametrize("rule", sorted(RULES))
def test_row_kernel_matches_scatter_path(rng, monkeypatch, rule, case):
    V, cap, make_ids, average = ROW_KERNEL_CASES[case]
    make_updater, make_state, steps = RULES[rule]
    sl = make_updater()
    feeds = [(jnp.asarray(np.asarray(make_ids(rng, V, cap), np.int32)),
              jnp.asarray(rng.standard_normal((cap, KD)).astype(np.float32)))
             for _ in range(steps)]
    p0, s0 = _table(rng, V)[0], make_state(rng, V)

    def run():
        p, s = p0, s0
        for ids, drows in feeds:
            p, s = sl.update(p, s, ids, drows, average=average)
        return p, s
    want = run()
    _through_kernel(monkeypatch)
    got = run()
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(g, w, rtol=1e-6, atol=1e-6)
    ids = np.concatenate([np.asarray(i) for i, _ in feeds])
    live = np.unique(ids[(ids >= 0) & (ids < V)])
    untouched = np.setdiff1d(np.arange(V), live)
    # the neighbours in a touched group of 8 are written back as they
    # were read: param, accumulator or both moments, bit for bit
    for g, x in zip(jax.tree.leaves(got), jax.tree.leaves((p0, s0))):
        if np.ndim(x) == 2:
            np.testing.assert_array_equal(np.asarray(g)[untouched],
                                          np.asarray(x)[untouched])
    if live.size:
        assert not np.array_equal(np.asarray(got[0])[live],
                                  np.asarray(p0)[live])


@pytest.mark.parametrize("rule", sorted(RULES))
def test_row_kernel_default_block_and_direct_call(rng, rule):
    """The kernel's own contract at its shipped block size: sorted,
    duplicate-free ids, ``n_valid`` of them live, the rest ignored
    whatever they hold."""
    V, cap, n_valid = 4008, 300, 170
    uids = np.full((cap,), V, np.int32)
    uids[:n_valid] = np.sort(rng.choice(V, size=n_valid, replace=False))
    gsum = jnp.asarray(rng.standard_normal((cap, KD)).astype(np.float32))
    p, a = _table(rng, V)
    live = jnp.asarray(np.where(np.arange(cap) < n_valid, uids, V))
    if rule == "adagrad":
        got = so.adagrad_rows(p, a, jnp.asarray(uids), jnp.int32(n_valid),
                              gsum, 0.2, 1e-7)
        want = so.SliceAdagrad(0.2)._scatter_rows(p, a, live, gsum)
    else:
        m, v, _ = _adam_state(rng, V)
        corr = jnp.asarray([1 - 0.9 ** 4, 1 - 0.999 ** 4], jnp.float32)
        got = so.adam_rows(p, m, v, jnp.asarray(uids), jnp.int32(n_valid),
                           gsum, corr, 0.05, 0.9, 0.999, 1e-8)
        want = so.SliceAdam(0.05)._scatter_rows(p, m, v, live, gsum, corr)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=1e-6, atol=1e-6)


def test_row_kernel_trajectory_matches_optax(rng, monkeypatch):
    """Five steps through the kernel against dense optax.adagrad."""
    _through_kernel(monkeypatch)
    V, cap, lr = 1006, 48, 0.3
    sl = so.SliceAdagrad(lr, initial_accumulator_value=0.1)
    tx = optax.adagrad(lr, initial_accumulator_value=0.1)
    p_k = p_d = jnp.asarray(rng.standard_normal((V, KD)).astype(np.float32))
    acc, st = sl.init(p_k), tx.init(p_d)
    touched = set()
    for _ in range(5):
        ids = rng.choice(V, size=cap).astype(np.int32)
        ids[0] = V - 1
        drows = rng.standard_normal((cap, KD)).astype(np.float32)
        g = np.zeros((V, KD), np.float32)
        np.add.at(g, ids, drows)
        touched |= set(ids.tolist())
        p_k, acc = sl.update(p_k, acc, jnp.asarray(ids), jnp.asarray(drows))
        u, st = tx.update(jnp.asarray(g), st, p_d)
        p_d = optax.apply_updates(p_d, u)
    rows = np.asarray(sorted(touched))
    np.testing.assert_allclose(np.asarray(p_k)[rows], np.asarray(p_d)[rows],
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(acc)[rows],
                               np.asarray(st[0].sum_of_squares)[rows],
                               rtol=1e-5, atol=1e-6)
    rest = np.setdiff1d(np.arange(V), rows)
    np.testing.assert_array_equal(np.asarray(p_k)[rest],
                                  np.asarray(p_d)[rest])


def test_adam_row_kernel_trajectory_is_lazy_adam(rng, monkeypatch):
    """Five steps through the kernel against lazy Adam written out in
    float64: a row's moments move only in the steps that touch it, the
    bias corrections follow the global count."""
    _through_kernel(monkeypatch)
    V, cap, lr, b1, b2, eps = 1006, 48, 0.05, 0.9, 0.999, 1e-8
    sl = so.SliceAdam(lr, b1=b1, b2=b2, eps=eps)
    p = jnp.asarray(rng.standard_normal((V, KD)).astype(np.float32))
    st = sl.init(p)
    ref_p, ref_m, ref_v = (np.asarray(p, np.float64), np.zeros((V, KD)),
                           np.zeros((V, KD)))
    for t in range(1, 6):
        ids = rng.choice(V // 2, size=cap).astype(np.int32)
        ids[0] = V - 1
        drows = rng.standard_normal((cap, KD)).astype(np.float32)
        g = np.zeros((V, KD))
        np.add.at(g, ids, drows.astype(np.float64))
        rows = np.unique(ids)
        ref_m[rows] = b1 * ref_m[rows] + (1 - b1) * g[rows]
        ref_v[rows] = b2 * ref_v[rows] + (1 - b2) * g[rows] ** 2
        ref_p[rows] -= lr * (ref_m[rows] / (1 - b1 ** t)) / (
            np.sqrt(ref_v[rows] / (1 - b2 ** t)) + eps)
        p, st = sl.update(p, st, jnp.asarray(ids), jnp.asarray(drows))
    assert int(st.count) == 5
    for got, ref in ((p, ref_p), (st.m, ref_m), (st.v, ref_v)):
        np.testing.assert_allclose(np.asarray(got), ref, rtol=1e-4,
                                   atol=1e-6)


# ---------------------------------------------------------------------------
# The combining (`_combine_slices`): the distinct ids, sorted, ahead of
# the sentinel V, and every slot's row summed onto its id's place,
# against NumPy's `unique` and `add.at`; and that it is made of sorts:
# an id-sized gather or scatter walks its indices one at a time on the
# chip (PERF.md section 6, PR 36).
# ---------------------------------------------------------------------------

def _zipf_ids(rng, V, cap):
    return (rng.zipf(1.3, size=cap) - 1) % V


def _ids_one_id(rng, V, cap):
    return np.full(cap, V // 3)


COMBINE_CASES = {
    # name: (V, slots, D, ids, average, grad_scale)
    "no_duplicates": (1000, 64, 8, _ids_all_live, False, 1.0),
    "every_id_the_same": (1000, 48, 8, _ids_one_id, False, 1.0),
    "negative_and_past_the_table": (1000, 96, 8, _ids_out_of_range,
                                    False, 1.0),
    "every_id_out_of_range": (1000, 32, 8, _ids_none_live, False, 1.0),
    "slots_not_a_multiple_of_8": (1000, 13, 3, _ids_duplicates, False, 1.0),
    "one_slot": (1000, 1, 4, _ids_duplicates, False, 1.0),
    "average": (1000, 96, 8, _ids_out_of_range, True, 1.0),
    "grad_scale": (1000, 96, 8, _ids_duplicates, False, 128.0),
    "average_and_grad_scale": (1000, 41, 8, _ids_duplicates, True, 0.25),
    "zipf_lm1b_emb_slots": (793470, 2560, 8, _zipf_ids, False, 1.0),
    "zipf_lm1b_softmax_slots": (793470, 10752, 8, _zipf_ids, False, 1.0),
}


def _combine_reference(ids, drows, V, average, grad_scale):
    key = np.where((ids >= 0) & (ids < V), ids, V)
    distinct, inv = np.unique(key, return_inverse=True)
    uids = np.full(ids.shape[0], V, np.int64)
    uids[:distinct.size] = distinct
    # float32 and in the slots' order, as the program sums
    gsum = np.zeros(drows.shape, np.float32)
    np.add.at(gsum, inv, drows * np.float32(grad_scale))
    if average:
        cnt = np.bincount(inv, minlength=ids.shape[0])
        gsum = gsum * (np.float32(1.0) / np.maximum(cnt, 1).astype(
            np.float32))[:, None]
    return uids, gsum


@pytest.mark.parametrize("case", sorted(COMBINE_CASES))
def test_combine_slices_matches_numpy_unique_and_add_at(rng, case):
    V, cap, D, make_ids, average, grad_scale = COMBINE_CASES[case]
    ids = np.asarray(make_ids(rng, V, cap)).astype(np.int32)
    drows = rng.standard_normal((cap, D)).astype(np.float32)
    uids, gsum = jax.jit(functools.partial(
        so._combine_slices, V=V, dtype=jnp.float32, average=average,
        grad_scale=grad_scale))(jnp.asarray(ids), jnp.asarray(drows))
    want_uids, want_gsum = _combine_reference(ids, drows, V, average,
                                              grad_scale)
    assert uids.dtype == jnp.int32 and gsum.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(uids), want_uids)
    n = int(np.sum(want_uids < V))
    assert np.all(np.diff(want_uids[:n]) > 0)      # sorted, distinct, then V
    scale = max(1.0, float(np.max(np.abs(want_gsum))))
    np.testing.assert_allclose(np.asarray(gsum), want_gsum, rtol=0,
                               atol=1e-6 * scale)
    if not average:
        # and to the bit what jnp.unique's inverse would have summed
        _, inv = jnp.unique(jnp.where((ids >= 0) & (ids < V), ids, V),
                            size=cap, fill_value=V, return_inverse=True)
        through_unique = jnp.zeros((cap, D), jnp.float32).at[
            inv.reshape(-1)].add(jnp.asarray(drows) * jnp.float32(grad_scale))
        np.testing.assert_array_equal(np.asarray(gsum),
                                      np.asarray(through_unique))


def _primitives(jaxpr, into):
    for eqn in jaxpr.eqns:
        into[eqn.primitive.name] += 1
        for v in eqn.params.values():
            inner = getattr(v, "jaxpr", v)
            if hasattr(inner, "eqns"):
                _primitives(inner, into)
    return into


@pytest.mark.parametrize("average", [False, True])
def test_combine_slices_is_sorts_and_one_scatter_add(average):
    """No id-sized array is walked by index: no `gather`, no `scatter`,
    one `scatter-add` (the rows' sum; the occurrence count is a second
    under `average`), at most three sorts."""
    jaxpr = jax.make_jaxpr(functools.partial(
        so._combine_slices, V=793470, dtype=jnp.float32, average=average))(
            jnp.zeros((10752,), jnp.int32), jnp.zeros((10752, 512)))
    count = _primitives(jaxpr.jaxpr, collections.Counter())
    assert count["gather"] == 0 and count["scatter"] == 0, count
    assert count["scatter-add"] == (2 if average else 1), count
    assert 1 <= count["sort"] <= 3, count


def _mesh(n):
    from jax.sharding import Mesh
    return Mesh(np.asarray(jax.devices()[:n]).reshape(1, n),
                ("repl", "shard"))


def _sds(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


# the VMEM the executor rule is told the core has: a (64, 128) float32
# table (32 KiB) is larger, an (8, 128) one is not
VMEM = 16 * 1024

EXECUTOR_CASES = {
    # name: (param, acc, devices in the mesh (None: no scope), backend)
    "held_in_vmem": (_sds((8, 128)), _sds((8, 128)), 1, "tpu"),
    "one_lane": (_sds((64, 1)), _sds((64, 1)), 1, "tpu"),
    "eight_lanes": (_sds((64, 8)), _sds((64, 8)), 1, "tpu"),
    "bf16_table": (_sds((64, 128), jnp.bfloat16), _sds((64, 128)), 1,
                   "tpu"),
    "cpu_backend": (_sds((64, 128)), _sds((64, 128)), 1, "cpu"),
    "sharded_over_8": (_sds((64, 128)), _sds((64, 128)), 8, "tpu"),
    "placement_unknown": (_sds((64, 128)), _sds((64, 128)), None, "tpu"),
}


@pytest.mark.parametrize("case", sorted(EXECUTOR_CASES))
@pytest.mark.parametrize("rule", sorted(RULES))
def test_row_executor_choice_keeps_the_scatter_path(monkeypatch, rule,
                                                   case):
    """Every table the kernel is not for records "xla" under either
    rule: by the rule, and by what a traced update on this backend
    notes."""
    monkeypatch.setattr(so, "_vmem_bytes", lambda: VMEM)
    param, acc, n_dev, backend = EXECUTOR_CASES[case]
    mesh = _mesh(n_dev) if n_dev else None
    assert so._row_executor(param, acc, mesh, backend) == "xla"
    # the one table that differs from these only in what they lack
    assert so._row_executor(_sds((64, 128)), _sds((64, 128)), _mesh(1),
                            "tpu") == "kernel"
    so.reset_trace_records()
    V, D = param.shape
    sl = RULES[rule][0]()
    p = jnp.zeros((V, D), param.dtype)
    a = (jnp.full((V, D), 0.1, acc.dtype) if rule == "adagrad"
         else sl.init(p))
    if n_dev and n_dev > 1:
        from jax.sharding import NamedSharding, PartitionSpec as P
        p = jax.device_put(p, NamedSharding(mesh, P("shard", None)))
        a = jax.tree.map(lambda x: jax.device_put(x, p.sharding)
                         if x.ndim == 2 else x, a)
    ids, drows = jnp.arange(8, dtype=jnp.int32), jnp.ones((8, D))

    def step(p, a):
        if mesh is None:
            return sl.update(p, a, ids, drows)
        with so.table_update_scope("t", mesh):
            return sl.update(p, a, ids, drows)
    jax.jit(step)(p, a)
    assert so.trace_records() == [
        {"table": "t" if mesh is not None else None, "rows": 8, "dim": D,
         "rule": rule, "executor": "xla"}]
    so.reset_trace_records()


@pytest.mark.parametrize("rule", sorted(RULES))
def test_trace_records_note_the_kernel_on_one_tpu(monkeypatch, rule):
    """A float32 lane-aligned table whole on a one-device mesh of a TPU,
    and larger than its VMEM, is noted as the kernel's, under the
    updater's rule: traced here for
    a TPU backend the test claims (nothing compiles or runs)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(so, "_vmem_bytes", lambda: VMEM)
    so.reset_trace_records()
    sl = RULES[rule][0]()
    p = jax.ShapeDtypeStruct((64, 256), jnp.float32)
    state = jax.eval_shape(sl.init, p)

    def step(p, state):
        with so.table_update_scope("emb", _mesh(1)):
            return sl.update(p, state, jnp.arange(24, dtype=jnp.int32),
                             jnp.ones((24, 256)))
    jaxpr = jax.make_jaxpr(step)(p, state)
    assert so.trace_records() == [
        {"table": "emb", "rows": 24, "dim": 256, "rule": rule,
         "executor": "kernel"}]
    assert f"name={rule}_rows" in str(jaxpr)
    so.reset_trace_records()
