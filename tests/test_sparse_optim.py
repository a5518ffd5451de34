"""Row-sparse optimizer updates (ops/sparse_optim.py) — scatter-only
adagrad parity with the reference's SparseApplyAdagrad semantics
(reference graph_transform_lib.py:71-77)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

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
