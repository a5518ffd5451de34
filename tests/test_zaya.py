"""models/zaya against the benchmark's plain float32 reference
(benchmark/reference/zaya1-8b.py) at a tiny size: loss, every position's
NLL, every gradient, every layer's choice; the carry between the
routers; the causality of the value shift and of both convolutions; the
chip's share of the experts; the tied table on the dense side; the
balancing biases; the model through ``parallel_run``."""

import collections
import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import parallax_tpu as parallax
from parallax_tpu.models import zaya

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def ref():
    path = os.path.join(ROOT, "benchmark", "reference", "zaya1-8b.py")
    spec = importlib.util.spec_from_file_location("zaya_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _as_dict(cfg):
    return {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}


def _setup(seed=0, batch_size=2, impls=(None, None), **kw):
    cfg = zaya.tiny_config(**kw)
    model = zaya.build_model(cfg, impls)
    params, state = model.init_fn(jax.random.PRNGKey(seed))
    # what starts at 0 or 1 moved off it, so that a missing term shows
    rng = np.random.default_rng(seed)
    for name, base in (("ln1", 1.0), ("ln2", 1.0), ("tau", 1.0),
                       ("r_gamma", 1.0), ("r_norm", 1.0), ("conv0_b", 0.0),
                       ("conv1_b", 0.0), ("r_bd", 0.0), ("r_b1", 0.0),
                       ("r_b2", 0.0)):
        shape = params["layers"][name].shape
        params["layers"][name] = jnp.asarray(
            base + 0.2 * rng.standard_normal(shape).astype(np.float32))
    beta = jnp.asarray(0.05 * rng.standard_normal(state["beta"].shape),
                       jnp.float32)
    batch = {k: jnp.asarray(v) for k, v in zaya.make_batch(
        rng, batch_size, cfg.seq_len, cfg.vocab_size).items()}
    return cfg, model, params, beta, batch


@pytest.mark.parametrize("attention", ["xla", "flash_interpret"])
def test_loss_nll_choices_and_every_gradient_match_the_reference(
        ref, attention):
    cfg, model, params, beta, batch = _setup(impls=(attention, None),
                                             num_layers=3)

    def system(p):
        return model.loss_fn(p, {"beta": beta}, batch, None)[0]

    loss, grads = jax.value_and_grad(system)(params)
    want_loss, want_grads = jax.value_and_grad(
        lambda p: ref.forward(p, batch, _as_dict(cfg), beta)[0])(params)
    np.testing.assert_allclose(float(loss), float(want_loss), rtol=1e-5)
    flat = jax.tree_util.tree_leaves_with_path(grads)
    for (path, got), want in zip(flat, jax.tree.leaves(want_grads)):
        assert float(jnp.abs(want).max()) > 0, path
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-3, atol=2e-6,
            err_msg=jax.tree_util.keystr(path))
    nll, scalars, picked = zaya.forward(cfg, params, beta, batch,
                                        (attention, None))
    _, out = ref.forward(params, batch, _as_dict(cfg), beta)
    np.testing.assert_allclose(np.asarray(nll), np.asarray(out["nll"]),
                               rtol=2e-5, atol=2e-6)
    np.testing.assert_array_equal(np.asarray(picked["choice"]),
                                  np.asarray(out["choice"]))
    np.testing.assert_allclose(np.asarray(picked["margin"]),
                               np.asarray(out["margin"]), atol=1e-5)
    np.testing.assert_array_equal(np.asarray(scalars["load"]),
                                  np.asarray(out["load"]))


def test_a_fed_choice_takes_the_routers_place_on_both_sides(ref):
    """The comparison under ONE routing: ``batch["expert_choice"]``
    routes every layer; the router's own ``argmax`` is still reported,
    and feeding it back changes nothing."""
    cfg, model, params, beta, batch = _setup(seed=2)
    nll, _, picked = zaya.forward(cfg, params, beta, batch)
    L, (B, T) = cfg.num_layers, batch["x"].shape
    same = {**batch, "expert_choice": picked["choice"].reshape(L, B, T)}
    again, _, _ = zaya.forward(cfg, params, beta, same)
    np.testing.assert_array_equal(np.asarray(again), np.asarray(nll))
    other = (picked["choice"].reshape(L, B, T) + 1) % cfg.num_experts
    fed = {**batch, "expert_choice": other}
    moved, scalars, own = zaya.forward(cfg, params, beta, fed)
    assert float(jnp.abs(moved - nll).max()) > 1e-4
    np.testing.assert_array_equal(np.asarray(own["choice"][0]),
                                  np.asarray(picked["choice"][0]))
    want = ref.forward(params, fed, _as_dict(cfg), beta)[1]
    np.testing.assert_allclose(np.asarray(moved), np.asarray(want["nll"]),
                               rtol=2e-5, atol=2e-6)
    np.testing.assert_array_equal(
        np.asarray(scalars["load"]),
        np.stack([np.bincount(np.asarray(o).ravel(),
                              minlength=cfg.num_experts) for o in other]))


def _scanned_loss(cfg, params, beta, batch):
    """The model's loss with the blocks under a ``lax.scan`` over the
    float32 stacks as they are, each under the model's own
    ``jax.checkpoint``: the reference of the one test below. ``(loss,
    per-layer scalars)``."""
    B, T = batch["x"].shape
    h = jnp.take(params["emb"], batch["x"], axis=0) * np.sqrt(cfg.model_dim)
    r0 = jnp.zeros((B * T, cfg.router_hidden_size), jnp.float32)

    def body(carry, xs):
        carry, scalars, _ = zaya._layer(cfg, *xs, *carry)
        return carry, scalars

    body = jax.checkpoint(
        body, policy=jax.checkpoint_policies.save_only_these_names(
            "flash_attn", "moe_rows"))
    (h, _), scalars = jax.lax.scan(body, (h.astype(cfg.compute_dtype), r0),
                                   (params["layers"], beta))
    hidden = zaya.rms_norm(h, params["final_norm"], cfg.rms_norm_eps)
    logits = hidden.reshape(B * T, -1) @ params["emb"].T
    logits = jnp.where(jnp.arange(logits.shape[-1]) < cfg.vocab_size,
                       logits, -jnp.inf)
    nll = -jnp.take_along_axis(jax.nn.log_softmax(logits),
                               batch["y"].reshape(B * T, 1), axis=-1)
    w = batch["w"].reshape(B * T)
    return jnp.sum(nll[:, 0] * w) / jnp.sum(w), scalars


def test_the_blocks_equal_a_plain_scan_and_the_stacks_keep_their_layout():
    """Loss, what the gauges read, the biases' step and every leaf's
    gradient of ``loss_fn`` against the same ``_layer`` under a
    ``lax.scan``, to float32 round-off; ``params["layers"]`` and its
    gradient stay stacked ``[L, ...]`` leaf by leaf."""
    cfg, model, params, beta, batch = _setup(seed=5, num_layers=3)
    L = cfg.num_layers

    def system(p):
        loss, metrics, state = model.loss_fn(p, {"beta": beta}, batch, None)
        return loss, (metrics, state)

    (loss, (metrics, state)), grads = jax.jit(jax.value_and_grad(
        system, has_aux=True))(params)
    (want, s), want_grads = jax.jit(jax.value_and_grad(
        lambda p: _scanned_loss(cfg, p, beta, batch), has_aux=True))(params)
    np.testing.assert_allclose(float(loss), float(want), rtol=1e-6)
    new_beta = zaya.balance_step(cfg, beta, s["load"])
    np.testing.assert_array_equal(np.asarray(state["beta"]),
                                  np.asarray(new_beta))
    for key, read in {
            "lm_loss": want, "moe_dropped": jnp.max(s["moe_dropped"]),
            "moe_rows_here": jnp.mean(s["moe_rows_here"]),
            "moe_rows_walked": jnp.mean(s["moe_rows_walked"]),
            "moe_load_max_over_mean": jnp.mean(s["moe_load_max_over_mean"]),
            "router_gate_mean": jnp.mean(s["gate_mean"]),
            "router_bias_abs_max": jnp.max(jnp.abs(new_beta))}.items():
        np.testing.assert_allclose(float(metrics[key]), float(read),
                                   rtol=1e-6, err_msg=key)
    assert set(metrics) == {"lm_loss"} | {
        out if isinstance(out, str) else out[0]
        for out in model.gauges.values()}
    assert jax.tree.structure(grads) == jax.tree.structure(params)
    flat = jax.tree_util.tree_leaves_with_path(grads)
    for (path, got), ref_g in zip(flat, jax.tree.leaves(want_grads)):
        scale = float(jnp.abs(ref_g).max())
        assert scale > 0, path
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref_g), rtol=1e-4,
            atol=1e-5 * scale, err_msg=jax.tree_util.keystr(path))
    for name, leaf in params["layers"].items():
        assert leaf.shape[0] == L, name
        assert grads["layers"][name].shape == leaf.shape, name
        assert grads["layers"][name].dtype == jnp.float32, name


def test_the_matrices_gradient_stacks_leave_the_loop_in_bfloat16():
    """The matrices' cast stands before the loop, so the backward scan
    hands their gradient stacks out in the compute dtype; every other
    leaf's in float32."""
    cfg, model, params, beta, batch = _setup(compute_dtype=jnp.bfloat16)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda p: model.loss_fn(p, {"beta": beta}, batch, None)[0]))(params)
    backward, = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"
                 and e.params["reverse"]]
    stacks = backward.outvars[backward.params["num_carry"]:]
    want = collections.Counter(
        (leaf.shape, "bfloat16" if name in zaya.MATRICES else "float32")
        for name, leaf in params["layers"].items())
    assert collections.Counter((v.aval.shape, str(v.aval.dtype))
                               for v in stacks) == want


def test_the_routers_state_is_carried_from_layer_to_layer():
    """Layer 1 reads layer 0's ``r``: with it zeroed its probabilities
    move and some tokens choose another expert; with ``r_gamma`` 0 the
    carry is cut and nothing moves."""
    cfg, _, params, beta, batch = _setup(seed=3, batch_size=4)
    p0, p1 = (jax.tree.map(lambda a: a[i], params["layers"])
              for i in range(2))
    B, T = batch["x"].shape
    h = (jnp.take(params["emb"], batch["x"], axis=0)
         * np.sqrt(cfg.model_dim))
    zero = jnp.zeros((B * T, cfg.router_hidden_size))
    (h1, r1), _, _ = zaya._layer(cfg, p0, beta[0], h, zero)
    assert float(jnp.abs(r1).max()) > 0
    (_, r2), _, with_carry = zaya._layer(cfg, p1, beta[1], h1, r1)
    (_, r2_cut), _, without = zaya._layer(cfg, p1, beta[1], h1, zero)
    assert float(jnp.abs(r2 - r2_cut).max()) > 1e-3
    differ = np.asarray(with_carry["choice"] != without["choice"])
    assert 0 < differ.sum() < differ.size
    no_gamma = {**p1, "r_gamma": jnp.zeros_like(p1["r_gamma"])}
    (_, r2_off), _, off = zaya._layer(cfg, no_gamma, beta[1], h1, r1)
    np.testing.assert_array_equal(np.asarray(r2_off), np.asarray(r2_cut))
    np.testing.assert_array_equal(np.asarray(off["choice"]),
                                  np.asarray(without["choice"]))


@pytest.mark.parametrize("t", [0, 5, 15])
def test_value_shift_and_both_convolutions_are_causal(t):
    """A change of the stream at position ``t`` moves no operand of the
    attention before ``t``; it moves q, k and the first value head at
    ``t``, and the second value head (the previous token's) only from
    ``t + 1`` on; through two taps twice, q and k feel it up to ``t +
    2`` and no further."""
    cfg, _, params, _, _ = _setup(seed=4)
    p = jax.tree.map(lambda a: a[0], params["layers"])
    rng = np.random.default_rng(t)
    T = cfg.seq_len
    u = jnp.asarray(rng.standard_normal((1, T, cfg.model_dim)), jnp.float32)
    bump = u.at[0, t].add(jnp.asarray(
        rng.standard_normal(cfg.model_dim), jnp.float32))
    q0, k0, v0 = zaya.cca_mix(cfg, p, u)
    q1, k1, v1 = zaya.cca_mix(cfg, p, bump)

    def moved(a, b):
        return np.asarray(jnp.abs(a - b).max(axis=(0, 2, 3)) > 0)

    for a, b in ((q0, q1), (k0, k1)):
        m = moved(a, b)
        assert not m[:t].any() and m[t:t + 3].all() and not m[t + 3:].any()
    first, second = moved(v0[:, :, :1], v1[:, :, :1]), \
        moved(v0[:, :, 1:], v1[:, :, 1:])
    assert first.tolist() == [i == t for i in range(T)]
    assert second.tolist() == [i == t + 1 for i in range(T)]


def test_the_two_halves_add_up_to_the_uncut_layer(ref):
    """Two chips hold two of four experts each (``first_expert`` 0 and
    2): what their layers add to the stream sums to what the uncut
    reference's whole layer adds, the attention and the router counted
    once."""
    cfg, _, params, beta, batch = _setup(seed=5, experts_held=4,
                                         num_layers=1, batch_size=4)
    p = jax.tree.map(lambda a: a[0], params["layers"])
    B, T = batch["x"].shape
    h = jnp.take(params["emb"], batch["x"], axis=0) * np.sqrt(cfg.model_dim)
    zero = jnp.zeros((B * T, cfg.router_hidden_size))
    with jax.default_matmul_precision("highest"):
        whole, r_whole, _ = ref._layer(_as_dict(cfg), p, beta[0], h, zero,
                                       None)
    (after_attention, _), _, _ = zaya._layer(
        cfg, {**p, "w_down": jnp.zeros_like(p["w_down"])}, beta[0], h, zero)
    added, rows = 0.0, 0.0
    for first in (0, 2):
        share = dataclasses.replace(cfg, experts_held=2, first_expert=first)
        cut = {**p, **{k: p[k][first:first + 2]
                       for k in ("w_gate", "w_up", "w_down")}}
        (out, r), scalars, _ = zaya._layer(share, cut, beta[0], h, zero)
        added = added + (out - after_attention)
        rows += float(scalars["moe_rows_here"])
        assert float(scalars["moe_dropped"]) == 0.0
        np.testing.assert_allclose(np.asarray(r), np.asarray(r_whole),
                                   rtol=2e-5, atol=2e-6)
    assert rows == B * T
    np.testing.assert_allclose(np.asarray(after_attention + added),
                               np.asarray(whole), rtol=2e-4, atol=2e-5)


def test_the_biases_move_towards_balance_and_take_no_gradient(ref):
    """``beta`` is model state: the step's own rule moves it against
    each expert's load, the routing follows, and no gradient reaches
    it."""
    cfg, model, params, _, batch = _setup(seed=6, batch_size=8,
                                          bias_update_rate=0.01)
    state = {"beta": jnp.zeros((cfg.num_layers, cfg.num_experts))}
    step = jax.jit(lambda s: model.loss_fn(params, s, batch, None))

    def spread(s):
        load = zaya.forward(cfg, params, s["beta"], batch)[1]["load"]
        return float(jnp.mean(jnp.max(load, axis=1) / jnp.mean(load, axis=1)))

    before = spread(state)
    _, _, new = step(state)
    load = zaya.forward(cfg, params, state["beta"], batch)[1]["load"]
    np.testing.assert_allclose(
        np.asarray(new["beta"]),
        ref.balance_step(state["beta"], load, 0.01), atol=1e-7)
    for _ in range(40):
        _, metrics, state = step(state)
    assert spread(state) < min(before, 1.3) and before > 1.3
    assert float(metrics["router_bias_abs_max"]) > 0.01
    d_beta = jax.grad(lambda b: model.loss_fn(params, {"beta": b}, batch,
                                              None)[0])(state["beta"])
    assert float(jnp.abs(d_beta).max()) == 0.0


def test_the_tied_table_is_dense_and_its_gradient_sums_both_uses(ref):
    """``emb`` is gathered and multiplied: the classifier's mixed-use
    branch puts it in the dense group, the engine keeps no slice state,
    and its gradient is the reference's, which ``jax.grad`` sums over
    the lookup's scatter-add and the head's product: a row whose id
    the batch never reads still has the head's gradient."""
    cfg, model, params, beta, batch = _setup(seed=7)
    sess, *_ = parallax.parallel_run(
        model, parallax_config=parallax.Config(
            run_option="HYBRID", search_partitions=False,
            shape_buckets=[8]), seed=3)
    feed = zaya.make_batch(np.random.default_rng(0), 8, cfg.seq_len,
                           cfg.vocab_size)
    sess.run("loss", feed_dict=feed)
    spec = sess.engine.plan.var_specs["emb"]
    assert not spec.is_sparse
    assert spec.reason == "gathered but also used densely"
    assert not sess.state.slice_state
    assert all(not s.is_sparse for s in sess.engine.plan.var_specs.values())
    sess.close()

    got = jax.grad(lambda p: model.loss_fn(p, {"beta": beta}, batch,
                                           None)[0])(params)["emb"]
    want = jax.grad(lambda p: ref.forward(p, batch, _as_dict(cfg),
                                          beta)[0])(params)["emb"]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-6)
    unread = np.setdiff1d(np.arange(cfg.vocab_size),
                          np.asarray(batch["x"]).ravel())
    assert unread.size and float(jnp.abs(got[unread]).min(axis=0).max()) > 0


def test_flop_count_of_the_published_model(ref):
    """The issue's count: 41.9 MFLOP a layer and 134 for the head
    forward, 1.16 GFLOP a trained token at the cell's sizes; the
    published model 3,254 MFLOP forward, a third of it the head."""
    cell = dict(_as_dict(zaya.ZayaConfig()), num_layers=6, experts_held=8,
                vocab_size=32784)
    assert ref.train_matmul_flops_per_token(cell) == pytest.approx(
        1.157e9, rel=2e-3)
    full = ref.train_matmul_flops_per_token(_as_dict(zaya.ZayaConfig())) / 3
    assert full == pytest.approx(3.254e9, rel=5e-3)
    head = 2 * 2048 * 262272
    assert head / full == pytest.approx(0.33, abs=0.005)


def test_trains_through_parallel_run_with_its_state_and_gauges():
    cfg = zaya.tiny_config(compute_dtype=jnp.bfloat16)
    sess, *_ = parallax.parallel_run(
        zaya.build_model(cfg),
        parallax_config=parallax.Config(
            run_option="HYBRID", search_partitions=False,
            shape_buckets=[8]),
        seed=3)
    batch = zaya.make_batch(np.random.default_rng(0), 8, cfg.seq_len,
                            cfg.vocab_size)
    losses = [float(sess.run("loss", feed_dict=batch)) for _ in range(12)]
    assert losses[-1] < losses[0] and all(np.isfinite(losses))
    out = sess.run(["lm_loss", "moe_dropped", "moe_rows_here",
                    "router_gate_mean", "router_bias_abs_max"],
                   feed_dict=batch)
    assert float(out[1]) == 0.0 and float(out[2]) > 0.0
    snap = sess.metrics_snapshot()
    assert snap["moe.dropped"] == 0.0
    assert snap["moe.rows_here"] == float(out[2])
    # one choice a token and every pair in one part: the per-token sums
    # fetch each token's own row (`ops/moe._sum_by_token`)
    assert snap["moe.rows_walked"] == batch["x"].size > snap["moe.rows_here"]
    assert snap["moe.load_max_over_mean"] >= 1.0
    assert 1.0 / cfg.num_experts < snap["router.gate_mean"] < 1.0
    assert snap["router.bias_abs_max"] == pytest.approx(float(out[4]))
    # thirteen steps of the rule have moved the biases, a step at a time
    beta = np.asarray(sess.state.model_state["beta"])
    assert beta.shape == (cfg.num_layers, cfg.num_experts)
    assert 0 < np.abs(beta).max() <= 13 * cfg.bias_update_rate + 1e-6
    # a caller may bring them to rest itself
    sess.set_model_state({"beta": np.zeros_like(beta)})
    assert float(np.abs(np.asarray(
        sess.state.model_state["beta"])).max()) == 0.0
    with pytest.raises(ValueError, match="structure"):
        sess.set_model_state({"beta": beta, "more": beta})
    sess.run("loss", feed_dict=batch)
    sess.close()


def test_a_share_the_router_lacks_is_refused():
    with pytest.raises(ValueError, match="router"):
        zaya.build_model(zaya.tiny_config(first_expert=3, experts_held=2))
    with pytest.raises(ValueError, match="one expert"):
        zaya.build_model(zaya.tiny_config(experts_per_token=2))


def _builder():
    path = os.path.join(ROOT, "benchmark", "builders", "zaya_train.py")
    spec = importlib.util.spec_from_file_location("zaya_builder", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("fault, caught_by", [
    (None, None),
    ("a small leaf the optimizer never reached", "leaf_change_least"),
    ("an update seven times the rate", "leaf_change_most"),
    ("the step dropped its new state", "biases_ok"),
    ("the rule applied twice a step", "biases_ok")])
def test_the_builders_window_check_holds_the_worst_leaf_and_the_biases(
        fault, caught_by):
    """``benchmark/builders/zaya_train.window_change``: every leaf's
    change in units of the scheduled rates' sum, the worst on either
    side named; the biases moved, by a step's worth a step at most. A
    frozen ``tau`` (12 numbers beside the experts' millions) reads 0
    whatever the other leaves did."""
    cfg = zaya.tiny_config(warmup_steps=1000, learning_rate=3e-4)
    params, state = zaya.build_model(cfg).init_fn(jax.random.PRNGKey(0))
    before = jax.tree.map(np.asarray, params)
    steps, first = 20, 2
    rate_sum = sum(3e-7 * t for t in range(first, first + steps))
    rng = np.random.default_rng(0)
    # Adam's step: an entry moves by about half the rate each step
    after = jax.tree.map(
        lambda a: a + 0.5 * rate_sum * rng.choice([-1.0, 1.0], a.shape)
        .astype(a.dtype), before)
    beta0 = np.asarray(state["beta"])
    beta1 = beta0 - cfg.bias_update_rate * steps * np.sign(
        rng.standard_normal(beta0.shape)).astype(np.float32)
    if fault == "a small leaf the optimizer never reached":
        after["layers"]["tau"] = before["layers"]["tau"]
    elif fault == "an update seven times the rate":
        after["layers"]["r_w3"] = before["layers"]["r_w3"] + 7 * (
            after["layers"]["r_w3"] - before["layers"]["r_w3"])
    elif fault == "the step dropped its new state":
        beta1 = beta0
    elif fault == "the rule applied twice a step":
        beta1 = beta0 + 2 * (beta1 - beta0)
    got = _builder().window_change(
        cfg, {"params": before, "beta": beta0, "step": np.int32(first)},
        {"params": after, "beta": beta1, "step": np.int32(first + steps)})
    assert got["steps"] == steps
    assert got["rate_sum"] == pytest.approx(rate_sum, rel=1e-5)
    assert set(got["leaf_change"]) >= {"emb", "final_norm", "layers/tau",
                                       "layers/r_w3", "layers/w_gate"}
    verdict = {"leaf_change_least": got["leaf_change_least"][1] > 0.05,
               "leaf_change_most": got["leaf_change_most"][1] < 2.0,
               "biases_ok": got["biases_ok"]}
    assert verdict == {k: k != caught_by for k in verdict}
    if fault == "a small leaf the optimizer never reached":
        assert got["leaf_change_least"] == ["layers/tau", 0.0]
    if fault is None:
        assert all(v == pytest.approx(0.5, rel=1e-3)
                   for v in got["leaf_change"].values())
        assert got["beta_moved_max"] == pytest.approx(
            got["beta_moved_steps_worth"], rel=1e-4)


@pytest.mark.parametrize("rows, spread, held", [
    (4138.7, 1.26, True),       # read under the configuration's schedule
    (5528.3, 3.20, False),      # the first trees' window: a collapse
    (4100.0, 2.40, False),      # one held expert far over the others
    (3500.0, 1.30, False),      # the rows gone elsewhere
    (None, None, False)])       # no gauge polled
def test_the_builder_holds_the_balance_at_the_windows_end(
        rows, spread, held):
    """``benchmark/builders/zaya_train.window_end``: the last step's
    rows and the fullest held expert against the limits the set-up's
    passes are held to."""
    polled = {} if rows is None else {
        "moe.rows_here": rows, "moe.load_max_over_mean": spread}
    got = _builder().window_end(
        polled, 4096.0,
        {"load_max_over_mean_max": 2.0, "rows_here_max": 0.1})
    assert got["held"] is held
    assert got["rows_here"] == rows and got["load_max_over_mean"] == spread
    if rows is not None:
        assert got["rows_here_over_held_share"] == pytest.approx(rows / 4096)
