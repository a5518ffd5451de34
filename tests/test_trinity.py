"""models/trinity against the benchmark's plain float32 reference
(benchmark/reference/trinity-mini.py) at a tiny size, one dense layer
and two periods of (sliding, sliding, full): loss, every position's NLL,
every leaf's gradient, every layer's top-k and the biases after a step;
no RoPE on a full layer; the window; the gate; the chip's share of the
experts with the shared expert counted once; the model through
``parallel_run`` with its ``model_state``."""

import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import parallax_tpu as parallax
from parallax_tpu.models import trinity
from parallax_tpu.models.decoder import rms_norm
from parallax_tpu.ops import moe as moe_ops

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SLIDING, FULL = trinity.SLIDING, trinity.FULL


@pytest.fixture(scope="module")
def ref():
    path = os.path.join(ROOT, "benchmark", "reference", "trinity-mini.py")
    spec = importlib.util.spec_from_file_location("trinity_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _as_dict(cfg):
    return {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}


def _setup(seed=0, batch_size=2, impls=(None, None), **kw):
    cfg = trinity.tiny_config(**kw)
    model = trinity.build_model(cfg, impls)
    params, state = model.init_fn(jax.random.PRNGKey(seed))
    # what starts at 1 or at 0 moved off it, so that a missing term shows
    rng = np.random.default_rng(seed)
    for stack in ("dense", "layers"):
        for name in ("ln1", "ln1_post", "ln2", "ln2_post", "q_norm",
                     "k_norm"):
            if stack in params:
                shape = params[stack][name].shape
                params[stack][name] = jnp.asarray(
                    1.0 + 0.2 * rng.standard_normal(shape).astype(np.float32))
    bias = jnp.asarray(0.05 * rng.standard_normal(
        state["router_bias"].shape).astype(np.float32))
    batch = {k: jnp.asarray(v) for k, v in trinity.make_batch(
        rng, batch_size, cfg.seq_len, cfg.vocab_size).items()}
    return cfg, model, params, bias, batch


def _layer(cfg, params, i):
    """The expert layer ``i``'s weights and its kind."""
    p = jax.tree.map(lambda a: a[i], params["layers"])
    tables = trinity.rope_tables(cfg)
    at = cfg.num_dense_layers + i
    return p, {"rope_w": tables["rope_w"][at],
               "is_window": bool(tables["is_window"][at])}


@pytest.mark.parametrize("impls", [("xla", None),
                                   ("flash_interpret", "gmm_interpret")],
                         ids=["xla", "kernels_interpreted"])
def test_loss_every_gradient_and_the_biases_match_the_reference(ref, impls):
    cfg, model, params, bias, batch = _setup(impls=impls, flash_tiles=(8, 8))
    assert cfg.kinds == (SLIDING,) + (SLIDING, SLIDING, FULL) * 2
    assert cfg.num_moe_layers == 6

    def loss_of(p):
        loss, metrics, state = model.loss_fn(p, {"router_bias": bias}, batch,
                                             None)
        return loss, (metrics, state)

    (loss, (metrics, state)), grads = jax.value_and_grad(
        loss_of, has_aux=True)(params)
    (want_loss, out), want_grads = jax.value_and_grad(
        lambda p: ref.forward(p, bias, batch, _as_dict(cfg)),
        has_aux=True)(params)
    np.testing.assert_allclose(float(loss), float(want_loss), rtol=1e-5)
    flat = jax.tree_util.tree_leaves_with_path(grads)
    assert len(flat) == 35
    for (path, got), want in zip(flat, jax.tree.leaves(want_grads)):
        assert float(jnp.abs(want).max()) > 0, path
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-3, atol=2e-6,
            err_msg=jax.tree_util.keystr(path))
    nll, s, choice = trinity.forward(cfg, params, bias, batch, impls)
    np.testing.assert_allclose(np.asarray(nll), np.asarray(out["nll"]),
                               rtol=2e-5, atol=2e-6)
    np.testing.assert_array_equal(np.sort(np.asarray(choice), axis=-1),
                                  np.sort(np.asarray(out["expert_choice"]),
                                          axis=-1))
    # the state no gradient reaches: the rule's step from the loads over
    # ALL the experts, not the held ones'
    np.testing.assert_array_equal(np.asarray(s["load"]),
                                  np.asarray(out["load"]))
    assert float(out["load"][0].sum()) \
        == batch["x"].size * cfg.experts_per_token
    want_bias = ref.balance_step(bias, out["load"], cfg.load_balance_coeff)
    np.testing.assert_allclose(np.asarray(state["router_bias"]), want_bias,
                               atol=1e-7)
    moved = np.abs(np.asarray(state["router_bias"]) - np.asarray(bias))
    assert moved.max() == pytest.approx(cfg.load_balance_coeff, rel=1e-4)
    spread = want_bias.max(axis=-1) - want_bias.min(axis=-1)
    assert float(metrics["router_bias_spread"]) == pytest.approx(
        spread.mean(), rel=1e-5)
    assert float(metrics["router_gate_sum_mean"]) == pytest.approx(
        float(jnp.mean(out["gate_sum_mean"])), rel=1e-5)


def test_a_fed_choice_takes_the_routers_place_on_both_sides(ref):
    """The comparison under ONE routing: ``batch["expert_choice"]``
    routes every expert layer, gates and loads with it; the router's own
    top-k is still reported, and feeding it back changes nothing."""
    cfg, model, params, bias, batch = _setup(seed=2)
    nll, _, choice = trinity.forward(cfg, params, bias, batch)
    L, k, (B, T) = cfg.num_moe_layers, cfg.experts_per_token, \
        batch["x"].shape
    same = {**batch, "expert_choice": choice.reshape(L, B, T, k)}
    again, _, _ = trinity.forward(cfg, params, bias, same)
    np.testing.assert_allclose(np.asarray(again), np.asarray(nll),
                               rtol=1e-6, atol=1e-6)
    fed = {**batch, "expert_choice":
           (choice.reshape(L, B, T, k) + 1) % cfg.num_experts}
    moved, s, own = trinity.forward(cfg, params, bias, fed)
    assert float(jnp.abs(moved - nll).max()) > 1e-4
    np.testing.assert_array_equal(np.asarray(own[0]), np.asarray(choice[0]))
    want_loss, out = ref.forward(params, bias, fed, _as_dict(cfg))
    np.testing.assert_allclose(np.asarray(moved), np.asarray(out["nll"]),
                               rtol=2e-5, atol=2e-6)
    # the loads are the fed routing's
    np.testing.assert_array_equal(np.asarray(s["load"]),
                                  np.asarray(out["load"]))
    got = jax.grad(lambda p: model.loss_fn(
        p, {"router_bias": bias}, fed, None)[0])(params)
    want = jax.grad(lambda p: ref.forward(p, bias, fed,
                                          _as_dict(cfg))[0])(params)
    for name in ("w_gate", "router", "shared_w_gate", "w_attn_gate"):
        np.testing.assert_allclose(
            np.asarray(got["layers"][name]),
            np.asarray(want["layers"][name]), rtol=2e-3, atol=2e-6)


def test_the_tables_turn_nothing_on_a_full_layer(ref):
    cfg = trinity.TrinityConfig()
    tables = trinity.rope_tables(cfg)
    w, window = np.asarray(tables["rope_w"]), np.asarray(tables["is_window"])
    assert w.shape == (32, 64) and window.sum() == 24
    assert not window[3::4].any() and not w[3::4].any()
    np.testing.assert_allclose(w[0], 1e4 ** (-np.arange(64) / 64), rtol=1e-6)
    want = ref.layer_tables(_as_dict(cfg))
    np.testing.assert_allclose(w, want["rope_w"], rtol=1e-6)
    assert (want["window"] >= cfg.seq_len).tolist() == (~window).tolist()
    assert set(want["window"][window]) == {2048}
    # the control reads the model as another's block
    blind = ref.layer_tables(_as_dict(cfg), as_another_models_block=True)
    assert blind["rope_w"][3].any() and blind["attn_gate_on"] == 0.0
    assert blind["shared_on"] == 0.0 and blind["sigmoid_router"] == 0.0


@pytest.mark.parametrize("layer,kind", [(0, SLIDING), (2, FULL)])
def test_only_a_sliding_layer_feels_the_positions(monkeypatch, layer, kind):
    """Every position moved, each by another step (``t -> 2 t + 3``): a
    full layer's output does not change, for it has no RoPE; a sliding
    layer's does."""
    cfg, _, params, _, batch = _setup(seed=4)
    assert cfg.kinds[cfg.num_dense_layers + layer] == kind
    p, own = _layer(cfg, params, layer)
    h = jnp.take(params["emb"], batch["x"], axis=0) * np.sqrt(cfg.model_dim)
    before = trinity.attention(cfg, p, own, h)
    plain = trinity.rope

    def moved(x, w, a):
        T = x.shape[1]
        far = plain(jnp.zeros((x.shape[0], 2 * T + 3) + x.shape[2:], x.dtype)
                    .at[:, 3::2].set(x), w, a)
        return far[:, 3::2]

    monkeypatch.setattr(trinity, "rope", moved)
    after = trinity.attention(cfg, p, own, h)
    change = float(jnp.abs(after - before).max())
    assert (change > 1e-3) if kind == SLIDING else (change == 0.0)


def test_a_query_never_reads_the_key_a_window_behind():
    """A sliding layer: the stream moved at position 3 reaches the
    queries 3 .. 3 + W - 1 and no later one; a full layer's reaches
    every later query."""
    cfg, _, params, _, batch = _setup(seed=5)
    W, s = cfg.sliding_window, 3
    h = jnp.take(params["emb"], batch["x"], axis=0) * np.sqrt(cfg.model_dim)
    bumped = h.at[:, s].add(1.0)
    for layer, reach in ((0, s + W), (2, cfg.seq_len)):
        p, own = _layer(cfg, params, layer)
        d = np.abs(np.asarray(trinity.attention(cfg, p, own, bumped)
                              - trinity.attention(cfg, p, own, h))
                   ).max(axis=(0, 2))
        assert not d[:s].any() and not d[reach:].any()
        assert (d[s:reach] > 1e-6).all()


def test_the_gate_at_zero_halves_the_attentions_output():
    """``Wg = 0``: ``sigmoid(0) = 1 / 2`` on every entry of ``o``. Read
    behind the output's norm, which at ``eps`` 1 does not hide a
    scale."""
    cfg, _, params, _, batch = _setup(seed=6, rms_norm_eps=1.0)
    p, own = _layer(cfg, params, 0)
    p = {**p, "w_attn_gate": jnp.zeros_like(p["w_attn_gate"])}
    h = jnp.take(params["emb"], batch["x"], axis=0) * np.sqrt(cfg.model_dim)
    B, T, _ = h.shape
    eps = cfg.rms_norm_eps
    a = rms_norm(h, p["ln1"], eps)
    q = rms_norm((a @ p["wq"]).reshape(B, T, cfg.num_heads, -1),
                 p["q_norm"], eps)
    k = rms_norm((a @ p["wk"]).reshape(B, T, cfg.num_kv_heads, -1),
                 p["k_norm"], eps)
    v = (a @ p["wv"]).reshape(B, T, cfg.num_kv_heads, -1)
    o = trinity._attend(cfg, trinity.rope(q, own["rope_w"], 1.0),
                        trinity.rope(k, own["rope_w"], 1.0), v, True, None)
    o = o.reshape(B, T, -1)
    got = trinity.attention(cfg, p, own, h)
    np.testing.assert_allclose(
        np.asarray(got),
        np.asarray(h + rms_norm((0.5 * o) @ p["wo"], p["ln1_post"], eps)),
        rtol=1e-5, atol=1e-6)
    whole = h + rms_norm(o @ p["wo"], p["ln1_post"], eps)
    assert float(jnp.abs(got - whole).max()) > 1e-3


@pytest.mark.parametrize("layer", [0, 2])
def test_the_eight_shares_add_up_to_the_uncut_layer(ref, layer):
    """Eight chips hold 1 of 8 experts each: the routed parts of their
    ``f`` and the shared expert ONCE sum to what the uncut reference's
    whole expert layer gives before its output norm; every chip's router
    sees all eight experts and the same loads."""
    cfg, _, params, bias, batch = _setup(seed=7, experts_held=8,
                                         batch_size=4)
    p, _ = _layer(cfg, params, layer)
    B, T = batch["x"].shape
    rows = jnp.asarray(np.random.default_rng(7).standard_normal(
        (B * T, cfg.model_dim)).astype(np.float32))
    switches = {k: jnp.float32(1.0) for k in ("attn_gate_on", "shared_on",
                                              "sigmoid_router")}
    with jax.default_matmul_precision("highest"):
        whole, picked = ref.expert_mix(_as_dict(cfg), p, bias[layer],
                                       switches, rows)
    shared = moe_ops.shared_expert(rows, p["shared_w_gate"],
                                   p["shared_w_up"], p["shared_w_down"])
    assert float(jnp.abs(shared).max()) > 1e-2
    routed, here = 0.0, 0.0
    for first in range(8):
        share = dataclasses.replace(cfg, experts_held=1, first_expert=first)
        cut = {**p, **{k: p[k][first:first + 1]
                       for k in ("w_gate", "w_up", "w_down")}}
        f, scalars, _ = trinity.expert_mix(share, cut, bias[layer], rows)
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


@pytest.mark.parametrize("dense", [0, 1, 2])
def test_zero_one_and_two_dense_layers_build(ref, dense):
    """The leading dense layers are a parameter tree of their own
    (absent where there is none), the expert layers the loop's."""
    cfg, model, params, bias, batch = _setup(
        num_dense_layers=dense, num_layers=dense + 3,
        layer_types=(SLIDING,) * dense + (SLIDING, SLIDING, FULL))
    assert ("dense" in params) == bool(dense)
    if dense:
        assert params["dense"]["w_up"].shape == (dense, 32, 48)
    assert params["layers"]["w_up"].shape == (3, 4, 32, 16)
    assert bias.shape == (3, cfg.num_experts)
    loss, _, state = model.loss_fn(params, {"router_bias": bias}, batch, None)
    want, _ = ref.forward(params, bias, batch, _as_dict(cfg))
    np.testing.assert_allclose(float(loss), float(want), rtol=1e-5)
    assert state["router_bias"].shape == bias.shape
    text = str(jax.make_jaxpr(lambda p: model.loss_fn(
        p, {"router_bias": bias}, batch, None)[0])(params))
    assert text.count("scan[") == 1


def test_the_expert_layers_are_one_scan_body_with_both_kinds_kernels():
    """ONE scan over the six expert layers, its body one ``cond``
    between the windowed and the plain forward call; the dense layer
    before it calls its own kind's kernel straight."""
    cfg, model, params, bias, batch = _setup(
        impls=("flash_interpret", None), flash_tiles=(8, 8))
    text = str(jax.make_jaxpr(lambda p: model.loss_fn(
        p, {"router_bias": bias}, batch, None)[0])(params))
    assert text.count("scan[") == 1
    assert text.count("name=flash_fwd_win") == 2
    assert text.count("name=flash_fwd\n") + text.count("name=flash_fwd ") \
        == 1


def test_trains_through_parallel_run_with_its_table_state_and_gauges():
    cfg = trinity.tiny_config(compute_dtype=jnp.bfloat16)
    sess, *_ = parallax.parallel_run(
        trinity.build_model(cfg),
        parallax_config=parallax.Config(
            run_option="HYBRID", sparse_grad_mode="slices",
            search_partitions=False, shape_buckets=[8]),
        seed=3)
    batch = trinity.make_batch(np.random.default_rng(0), 8, cfg.seq_len,
                               cfg.vocab_size)
    losses = [float(sess.run("loss", feed_dict=batch)) for _ in range(12)]
    assert losses[-1] < losses[0] and all(np.isfinite(losses))
    assert sorted(sess.state.slice_state) == ["emb"]
    # the biases are the engine's model_state: twelve steps moved them,
    # each by at most the rule's step
    moved = np.abs(np.asarray(sess.state.model_state["router_bias"]))
    assert moved.shape == (cfg.num_moe_layers, cfg.num_experts)
    assert 0.0 < moved.max() <= 12 * cfg.load_balance_coeff * 1.0001
    out = sess.run(["lm_loss", "moe_dropped", "moe_rows_here"],
                   feed_dict=batch)
    assert float(out[1]) == 0.0 and float(out[2]) > 0.0
    snap = sess.metrics_snapshot()
    assert snap["moe.dropped"] == 0.0
    assert snap["moe.rows_here"] == float(out[2])
    assert snap["moe.rows_walked"] == snap["moe.rows_here"]
    assert snap["moe.load_max_over_mean"] >= 1.0
    assert 0.0 < snap["router.bias_spread"] <= 26 * cfg.load_balance_coeff
    assert 0.0 < snap["router.gate_sum_mean"] < cfg.experts_per_token
    sess.close()


@pytest.mark.parametrize("table_rate,want",
                         [(None, 3e-4), (6.63e-6, 6.63e-6)])
def test_the_tables_rate_is_the_configurations(table_rate, want):
    """Lazy Adam on the table at ``table_learning_rate``, a constant;
    ``learning_rate`` where none is given."""
    model = trinity.build_model(trinity.tiny_config(
        learning_rate=3e-4, table_learning_rate=table_rate))
    assert sorted(model.slice_updaters) == ["emb"]
    assert model.slice_updaters["emb"].learning_rate == want


def test_flop_count_of_the_cell_and_of_the_published_model(ref):
    """A sliding layer's attention counts ITS pairs (14,681,088 a head
    at 8,192 under 2,048: 43.7 % of a full layer's 33,558,528); the head
    is 13.9 % of the cell's forward matrix work and 11.1 % of the
    published model's."""
    assert ref.attended_pairs(8192, 2048) == 14681088
    assert ref.attended_pairs(8192, 8192) == 33558528
    full = _as_dict(trinity.TrinityConfig())
    cell = dict(full, num_layers=5, num_dense_layers=1, experts_held=16,
                vocab_size=25024,
                layer_types=(SLIDING,) + (SLIDING, SLIDING, SLIDING, FULL))
    D, T = 2048, 8192
    proj = 2 * D * (3 * 32 * 128 + 2 * 4 * 128)
    sliding = 2 * 2 * 32 * 128 * 14681088 / T
    causal = 2 * 2 * 32 * 128 * 33558528 / T
    dense = proj + sliding + 3 * 2 * D * 6144
    sparse = proj + 2 * D * 128 + 2 * 3 * 2 * D * 1024
    assert proj == pytest.approx(54.5e6, rel=2e-3)
    assert sparse == pytest.approx(80.2e6, rel=2e-3)
    layers = dense + 4 * sparse + 3 * sliding + causal
    head = 2 * D * 25024
    assert ref.train_matmul_flops_per_token(cell) == pytest.approx(
        3 * (layers + head), rel=1e-9)
    assert head / (layers + head) == pytest.approx(0.139, abs=0.002)
    whole = ref.train_matmul_flops_per_token(full) / 3
    assert 2 * D * 200192 / whole == pytest.approx(0.111, abs=0.002)


@pytest.mark.parametrize("kw", [
    dict(experts_held=4, first_expert=6),
    dict(layer_types=("sliding_attention", "linear_attention")),
    dict(layer_types=(SLIDING,) * 4),
    dict(layer_types=()),
    dict(num_heads=3),
    dict(num_dense_layers=7),
    dict(num_shared_experts=0),
])
def test_a_config_the_model_cannot_be_is_refused(kw):
    with pytest.raises(ValueError):
        trinity.build_model(trinity.tiny_config(**kw))
