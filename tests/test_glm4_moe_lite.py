"""models/glm4_moe_lite against the benchmark's plain float32 reference
(benchmark/reference/glm-4.7-flash.py) at a tiny size, one dense layer,
three expert layers and one MTP block: loss, every position's NLL of
both streams, every leaf's gradient, every layer's top-k and the biases
after a step; the chip's share of the experts with the shared expert
counted once; the table looked up twice and updated once; the model
through ``parallel_run`` with its ``model_state``."""

import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import parallax_tpu as parallax
from parallax_tpu.models import glm4_moe_lite as glm
from parallax_tpu.models.decoder import expert_mix
from parallax_tpu.ops import moe as moe_ops

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def ref():
    path = os.path.join(ROOT, "benchmark", "reference", "glm-4.7-flash.py")
    spec = importlib.util.spec_from_file_location("glm_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _as_dict(cfg):
    return {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}


def _setup(seed=0, batch_size=2, impls=(None, None), **kw):
    cfg = glm.tiny_config(**kw)
    model = glm.build_model(cfg, impls)
    params, state = model.init_fn(jax.random.PRNGKey(seed))
    # what starts at 1 or at 0 moved off it, so that a missing term shows
    rng = np.random.default_rng(seed)
    norms = ("ln1", "ln2", "q_a_norm", "kv_a_norm")
    for stack, names in (("dense", norms), ("layers", norms),
                         ("mtp", norms + ("enorm", "hnorm", "final_norm"))):
        for name in names:
            if stack in params:
                shape = params[stack][name].shape
                params[stack][name] = jnp.asarray(
                    1.0 + 0.2 * rng.standard_normal(shape).astype(np.float32))
    bias = jnp.asarray(0.05 * rng.standard_normal(
        state["router_bias"].shape).astype(np.float32))
    batch = {k: jnp.asarray(v) for k, v in glm.make_batch(
        rng, batch_size, cfg.seq_len, cfg.vocab_size).items()}
    return cfg, model, params, bias, batch


def _loss(model, params, bias, batch):
    loss, metrics, state = model.loss_fn(params, {"router_bias": bias}, batch,
                                         None)
    return loss, (metrics, state)


@pytest.mark.parametrize("impls", [("xla", None),
                                   ("flash_interpret", "gmm_interpret")],
                         ids=["xla", "kernels_interpreted"])
def test_loss_every_gradient_and_the_biases_match_the_reference(ref, impls):
    cfg, model, params, bias, batch = _setup(impls=impls, flash_tiles=(8, 8))
    assert cfg.num_moe_layers == 3 and bias.shape == (4, cfg.num_experts)
    (loss, (metrics, state)), grads = jax.value_and_grad(
        lambda p: _loss(model, p, bias, batch), has_aux=True)(params)
    (want_loss, out), want_grads = jax.value_and_grad(
        lambda p: ref.forward(p, bias, batch, _as_dict(cfg)),
        has_aux=True)(params)
    np.testing.assert_allclose(float(loss), float(want_loss), rtol=1e-5)
    flat = jax.tree_util.tree_leaves_with_path(grads)
    # emb, head, final_norm; 12 dense, 16 expert and 20 MTP leaves
    assert len(flat) == 51
    for (path, got), want in zip(flat, jax.tree.leaves(want_grads)):
        assert float(jnp.abs(want).max()) > 0, path
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-3, atol=2e-6,
            err_msg=jax.tree_util.keystr(path))
    nll, mtp_nll, s, choice = glm.forward(cfg, params, bias, batch, impls)
    np.testing.assert_allclose(np.asarray(nll), np.asarray(out["nll"]),
                               rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(np.asarray(mtp_nll),
                               np.asarray(out["mtp_nll"]), rtol=2e-5,
                               atol=2e-6)
    np.testing.assert_array_equal(np.sort(np.asarray(choice), axis=-1),
                                  np.sort(np.asarray(out["expert_choice"]),
                                          axis=-1))
    # the losses the step reports: the main stream's and the MTP's
    main = float(jnp.mean(out["nll"]))
    mtp = float(jnp.mean(out["mtp_nll"][:, :-1]))
    assert float(metrics["lm_loss"]) == pytest.approx(main, rel=1e-5)
    assert float(metrics["mtp_nll"]) == pytest.approx(mtp, rel=1e-5)
    assert float(loss) == pytest.approx(main + 0.3 * mtp, rel=1e-5)
    # the state no gradient reaches, the MTP layer's biases last: the
    # rule's step from the loads over ALL the experts
    np.testing.assert_array_equal(np.asarray(s["load"]),
                                  np.asarray(out["load"]))
    want_bias = ref.balance_step(bias, out["load"], cfg.load_balance_coeff)
    np.testing.assert_allclose(np.asarray(state["router_bias"]), want_bias,
                               atol=1e-7)


def test_a_fed_choice_takes_the_routers_place_on_both_sides(ref):
    """The comparison under ONE routing: ``batch["expert_choice"]``
    routes every expert layer, the MTP block's among them; the router's
    own top-k is still reported."""
    cfg, model, params, bias, batch = _setup(seed=2)
    nll, mtp_nll, _, choice = glm.forward(cfg, params, bias, batch)
    L, k, (B, T) = cfg.num_moe_layers + 1, cfg.experts_per_token, \
        batch["x"].shape
    same = {**batch, "expert_choice": choice.reshape(L, B, T, k)}
    again, again_mtp, _, _ = glm.forward(cfg, params, bias, same)
    np.testing.assert_allclose(np.asarray(again), np.asarray(nll), rtol=1e-6,
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(again_mtp), np.asarray(mtp_nll),
                               rtol=1e-6, atol=1e-6)
    fed = {**batch, "expert_choice":
           (choice.reshape(L, B, T, k) + 1) % cfg.num_experts}
    moved, moved_mtp, s, own = glm.forward(cfg, params, bias, fed)
    assert float(jnp.abs(moved - nll).max()) > 1e-4
    assert float(jnp.abs(moved_mtp - mtp_nll).max()) > 1e-4
    # the first expert layer's stream is the routing's to move only after
    np.testing.assert_array_equal(np.asarray(own[0]), np.asarray(choice[0]))
    _, out = ref.forward(params, bias, fed, _as_dict(cfg))
    np.testing.assert_allclose(np.asarray(moved), np.asarray(out["nll"]),
                               rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(np.asarray(moved_mtp),
                               np.asarray(out["mtp_nll"]), rtol=2e-5,
                               atol=2e-6)
    np.testing.assert_array_equal(np.asarray(s["load"]),
                                  np.asarray(out["load"]))


def test_one_rotary_key_serves_every_head():
    """``k_r`` is one key a position: with ``W_kva``'s rotary columns at
    zero every head's scores lose the same rotary term, and the gradient
    of those columns is the sum over the heads."""
    cfg, _, params, _, batch = _setup(seed=3)
    p = jax.tree.map(lambda a: a[0], params["layers"])
    h = jnp.take(params["emb"], batch["x"], axis=0)
    R = cfg.kv_lora_rank

    def out(wkv_a):
        return jnp.sum(glm.attention(cfg, {**p, "wkv_a": wkv_a}, h) ** 2)

    g = jax.grad(out)(p["wkv_a"])
    assert g.shape == (cfg.model_dim, R + cfg.qk_rope_head_dim)
    assert float(jnp.abs(g[:, R:]).max()) > 1e-4
    # a head's q_rope at zero: the rotary key then reaches no score of
    # that head, and with every head's at zero none at all
    H, dn, dr = cfg.num_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    wq_b = p["wq_b"].reshape(cfg.q_lora_rank, H, dn + dr).at[..., dn:].set(0)
    g = jax.grad(lambda w: jnp.sum(glm.attention(
        cfg, {**p, "wkv_a": w, "wq_b": wq_b.reshape(p["wq_b"].shape)},
        h) ** 2))(p["wkv_a"])
    assert float(jnp.abs(g[:, R:]).max()) == 0.0


def test_the_mtp_targets_are_the_labels_shifted_once_more():
    """The block reads ``x_{t+1} = y_t`` and predicts ``y_{t+1}``; the
    last position has no label and weighs 0, and a weight of the batch
    moves with its label; without ``w`` every other position weighs
    1."""
    y = jnp.arange(12).reshape(2, 6)
    w = jnp.ones((2, 6)).at[1, 2].set(0.0)
    ids, labels, weight = glm.mtp_targets({"y": y, "w": w})
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(y))
    np.testing.assert_array_equal(np.asarray(labels[:, :-1]),
                                  np.asarray(y[:, 1:]))
    np.testing.assert_array_equal(np.asarray(weight),
                                  [[1, 1, 1, 1, 1, 0], [1, 0, 1, 1, 1, 0]])
    _, _, weight = glm.mtp_targets({"y": y})
    np.testing.assert_array_equal(np.asarray(weight),
                                  [[1, 1, 1, 1, 1, 0]] * 2)


@pytest.mark.parametrize("layer", [0, "mtp"])
def test_the_eight_shares_add_up_to_the_uncut_layer(ref, layer):
    """Eight chips hold 1 of 8 experts each: the routed parts of their
    mix and the shared expert ONCE sum to what the uncut reference's
    whole expert layer adds; every chip's router sees all eight experts
    and the same loads."""
    cfg, _, params, bias, batch = _setup(seed=7, experts_held=8,
                                         batch_size=4)
    p, b = (jax.tree.map(lambda a: a[0], params["layers"]), bias[0]) \
        if layer == 0 else (params["mtp"], bias[-1])
    B, T = batch["x"].shape
    rows = jnp.asarray(np.random.default_rng(7).standard_normal(
        (B * T, cfg.model_dim)).astype(np.float32))
    with jax.default_matmul_precision("highest"):
        whole, picked = ref.expert_mix(_as_dict(cfg), p, b, rows)
    shared = moe_ops.shared_expert(rows, p["shared_w_gate"],
                                   p["shared_w_up"], p["shared_w_down"])
    assert float(jnp.abs(shared).max()) > 1e-2
    routed, here = 0.0, 0.0
    for first in range(8):
        share = dataclasses.replace(cfg, experts_held=1, first_expert=first)
        cut = {**p, **{k: p[k][first:first + 1]
                       for k in ("w_gate", "w_up", "w_down")}}
        f, scalars, _ = expert_mix(share, cut, b, rows)
        routed = routed + (f - shared)
        here += float(scalars["moe_rows_here"])
        assert float(scalars["moe_dropped"]) == 0.0
        np.testing.assert_array_equal(np.asarray(scalars["load"]),
                                      np.asarray(picked["load"]))
    assert here == B * T * cfg.experts_per_token
    np.testing.assert_allclose(np.asarray(shared + routed),
                               np.asarray(whole), rtol=2e-4, atol=2e-5)
    # counted eight times it is another layer
    assert float(jnp.abs(8 * shared + routed - whole).max()) > 1e-2


def test_the_table_looked_up_twice_gets_one_lazy_adam_update(ref):
    """The stream's lookup of ``x`` and the MTP block's of ``y`` are two
    events of one table; the step combines them: the touched rows' first
    moment is ``(1 - b1)`` times the SUM of both events' row gradients
    (the table's whole gradient by ``jax.grad``) and the rows moved by
    ONE lazy Adam step of that sum."""
    cfg = glm.tiny_config(table_learning_rate=1e-2)
    model = glm.build_model(cfg)
    sess, *_ = parallax.parallel_run(
        model, parallax_config=parallax.Config(
            run_option="HYBRID", sparse_grad_mode="slices",
            search_partitions=False, shape_buckets=[8]),
        seed=5)
    batch = glm.make_batch(np.random.default_rng(5), 8, cfg.seq_len,
                           cfg.vocab_size)
    sess.prepare(batch)
    before = jax.device_get(sess.state.params)
    bias = jax.device_get(sess.state.model_state["router_bias"])
    sess.run("loss", feed_dict=batch)
    after = jax.device_get(sess.state.params)["emb"]
    adam = jax.device_get(sess.state.slice_state["emb"])
    sess.close()

    def loss_of(emb, lookups):
        # the table's gradient through the chosen lookups only
        ids = {"x": batch["x"], "y": batch["y"]}
        parts = {k: jnp.take(emb, v, axis=0) if k in lookups
                 else jax.lax.stop_gradient(jnp.take(emb, v, axis=0))
                 for k, v in ids.items()}
        real = glm.emb_ops.embedding_lookup
        calls = iter([parts["x"], parts["y"]])
        glm.emb_ops.embedding_lookup = lambda table, i: next(calls)
        try:
            return model.loss_fn({**before, "emb": emb},
                                 {"router_bias": bias}, batch, None)[0]
        finally:
            glm.emb_ops.embedding_lookup = real

    g = np.asarray(jax.grad(loss_of)(before["emb"], ("x", "y")))
    g_x = np.asarray(jax.grad(loss_of)(before["emb"], ("x",)))
    touched = np.unique(np.concatenate([batch["x"].ravel(),
                                        batch["y"].ravel()]))
    # the MTP block's lookup reaches the table: its rows' gradient is
    # not the stream's alone
    assert np.abs(g - g_x)[touched].max() > 1e-3 * np.abs(g).max()
    # the benchmark's reference lazy Adam from zero moments, at the
    # table's rate; float32 sums in another order: a part in 1e5 of the
    # largest
    zero = np.zeros_like(g[touched])
    want, m, v = ref.lazy_adam_rows(before["emb"][touched], zero, zero, 0,
                                    g[touched], 1e-2)
    np.testing.assert_allclose(adam.m[touched], m, rtol=2e-3,
                               atol=1e-5 * np.abs(m).max())
    np.testing.assert_allclose(adam.v[touched], v, rtol=4e-3,
                               atol=1e-5 * v.max())
    assert int(adam.count) == 1
    # one step at t = 1: m_hat = g, v_hat = g^2, a move of the rate
    np.testing.assert_allclose(np.abs(want - before["emb"][touched]), 1e-2,
                               rtol=1e-3)
    np.testing.assert_allclose(after[touched], want, rtol=1e-5, atol=2e-6)
    untouched = np.setdiff1d(np.arange(after.shape[0]), touched)
    np.testing.assert_array_equal(after[untouched],
                                  before["emb"][untouched])


def test_the_expert_layers_are_one_scan_and_mtp_is_straight_line():
    """ONE scan over the three expert layers; the dense layer before it
    and the MTP block after it call the flash kernels straight."""
    cfg, model, params, bias, batch = _setup(
        impls=("flash_interpret", None), flash_tiles=(8, 8))
    text = str(jax.make_jaxpr(lambda p: _loss(model, p, bias, batch)[0])(
        params))
    assert text.count("scan[") == 1
    assert text.count("name=flash_fwd") == 3


def test_without_mtp_the_loss_is_the_streams_alone():
    cfg, model, params, bias, batch = _setup(num_mtp_layers=0)
    assert "mtp" not in params and bias.shape == (3, cfg.num_experts)
    loss, (metrics, state) = _loss(model, params, bias, batch)
    nll, mtp_nll, _, _ = glm.forward(cfg, params, bias, batch)
    assert mtp_nll is None
    assert float(loss) == pytest.approx(float(jnp.mean(nll)), rel=1e-6)
    assert float(metrics["mtp_nll"]) == 0.0


def test_trains_through_parallel_run_with_its_table_state_and_gauges():
    cfg = glm.tiny_config(compute_dtype=jnp.bfloat16)
    sess, *_ = parallax.parallel_run(
        glm.build_model(cfg),
        parallax_config=parallax.Config(
            run_option="HYBRID", sparse_grad_mode="slices",
            search_partitions=False, shape_buckets=[8]),
        seed=3)
    batch = glm.make_batch(np.random.default_rng(0), 8, cfg.seq_len,
                           cfg.vocab_size)
    losses = [float(sess.run("loss", feed_dict=batch)) for _ in range(12)]
    assert losses[-1] < losses[0] and all(np.isfinite(losses))
    assert sorted(sess.state.slice_state) == ["emb"]
    moved = np.abs(np.asarray(sess.state.model_state["router_bias"]))
    assert moved.shape == (cfg.num_moe_layers + 1, cfg.num_experts)
    assert 0.0 < moved.max() <= 12 * cfg.load_balance_coeff * 1.0001
    out = sess.run(["lm_loss", "mtp_nll", "moe_dropped"], feed_dict=batch)
    assert float(out[2]) == 0.0
    snap = sess.metrics_snapshot()
    assert snap["mtp.nll"] == pytest.approx(float(out[1]))
    assert snap["mtp.nll"] > float(out[0]) > 0.0
    assert snap["moe.dropped"] == 0.0
    assert 0.0 < snap["router.bias_spread"] <= 26 * cfg.load_balance_coeff
    sess.close()


def test_flop_count_of_the_cell(ref):
    """The cell's step: 3.6 GFLOP a token, of which the attention's two
    products over the causal pairs at 20 heads of 256 are 42 %; the
    latent attention's five products are 21.76 M parameters a layer."""
    cell = dict(_as_dict(glm.GlmConfig()), num_layers=5, experts_held=8,
                vocab_size=19360)
    flops = ref.train_matmul_flops_per_token(cell)
    assert flops == pytest.approx(3.62e9, rel=5e-3)
    pairs = 2 * 20 * (192 + 64 + 256) * 8193 / 2
    assert 3 * 6 * pairs / flops == pytest.approx(0.417, abs=0.003)
    proj = 2048 * 768 + 768 * 20 * 256 + 2048 * 576 + 512 * 20 * 448 \
        + 5120 * 2048
    assert proj == pytest.approx(21.76e6, rel=1e-3)
    dense = ref.layer_flops(cell, True)
    assert dense == pytest.approx(2 * proj + pairs + 6 * 2048 * 10240)


@pytest.mark.parametrize("kw", [
    dict(experts_held=4, first_expert=6),
    dict(v_head_dim=12),
    dict(qk_nope_head_dim=8),
    dict(qk_rope_head_dim=5, qk_nope_head_dim=11),
    dict(num_dense_layers=4),
    dict(num_shared_experts=0),
    dict(num_mtp_layers=2),
])
def test_a_config_the_model_cannot_be_is_refused(kw):
    with pytest.raises(ValueError):
        glm.build_model(glm.tiny_config(**kw))
