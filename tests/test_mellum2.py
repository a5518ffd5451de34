"""models/mellum2 against the benchmark's plain float32 reference
(benchmark/reference/mellum2-12b-a2.5b.py) at a tiny size with TWO
periods of (sliding, sliding, full): loss, every position's NLL, every
gradient, every layer's top-k; the YaRN table at the published sizes;
two kinds of layer under one loop body; the chip's share of the experts;
the model through ``parallel_run``."""

import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import parallax_tpu as parallax
from parallax_tpu.models import mellum2

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def ref():
    path = os.path.join(ROOT, "benchmark", "reference",
                        "mellum2-12b-a2.5b.py")
    spec = importlib.util.spec_from_file_location("mellum2_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _as_dict(cfg):
    return {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}


def _setup(seed=0, batch_size=2, impls=(None, None), **kw):
    cfg = mellum2.tiny_config(**kw)
    model = mellum2.build_model(cfg, impls)
    params = model.init_fn(jax.random.PRNGKey(seed))
    # what starts at 1 moved off it, so that a missing term shows
    rng = np.random.default_rng(seed)
    for name in ("ln1", "ln2", "q_norm", "k_norm"):
        shape = params["layers"][name].shape
        params["layers"][name] = jnp.asarray(
            1.0 + 0.2 * rng.standard_normal(shape).astype(np.float32))
    batch = {k: jnp.asarray(v) for k, v in mellum2.make_batch(
        rng, batch_size, cfg.seq_len, cfg.vocab_size).items()}
    return cfg, model, params, batch


@pytest.mark.parametrize("attention", ["xla", "flash_interpret"])
def test_loss_nll_choices_and_every_gradient_match_the_reference(
        ref, attention):
    cfg, model, params, batch = _setup(impls=(attention, None),
                                       flash_tiles=(8, 8))
    assert cfg.kinds == (mellum2.SLIDING, mellum2.SLIDING, mellum2.FULL) * 2

    loss, grads = jax.value_and_grad(
        lambda p: model.loss_fn(p, batch, None)[0])(params)
    want_loss, want_grads = jax.value_and_grad(
        lambda p: ref.forward(p, batch, _as_dict(cfg))[0])(params)
    np.testing.assert_allclose(float(loss), float(want_loss), rtol=1e-5)
    flat = jax.tree_util.tree_leaves_with_path(grads)
    for (path, got), want in zip(flat, jax.tree.leaves(want_grads)):
        assert float(jnp.abs(want).max()) > 0, path
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-3, atol=2e-6,
            err_msg=jax.tree_util.keystr(path))
    nll, _, choice = mellum2.forward(cfg, params, batch, (attention, None))
    _, out = ref.forward(params, batch, _as_dict(cfg))
    np.testing.assert_allclose(np.asarray(nll), np.asarray(out["nll"]),
                               rtol=2e-5, atol=2e-6)
    np.testing.assert_array_equal(np.sort(np.asarray(choice), axis=-1),
                                  np.sort(np.asarray(out["expert_choice"]),
                                          axis=-1))


def test_a_fed_choice_takes_the_routers_place_on_both_sides(ref):
    """The comparison under ONE routing: ``batch["expert_choice"]``
    routes every layer, gates and loads with it; the router's own top-k
    is still reported, and feeding it back changes nothing."""
    cfg, model, params, batch = _setup(seed=2)
    nll, _, choice = mellum2.forward(cfg, params, batch)
    L, k, (B, T) = cfg.num_layers, cfg.experts_per_token, batch["x"].shape
    same = {**batch, "expert_choice": choice.reshape(L, B, T, k)}
    again, _, _ = mellum2.forward(cfg, params, same)
    np.testing.assert_allclose(np.asarray(again), np.asarray(nll),
                               rtol=1e-6, atol=1e-6)
    other = (choice.reshape(L, B, T, k) + 1) % cfg.num_experts
    fed = {**batch, "expert_choice": other}
    moved, _, own = mellum2.forward(cfg, params, fed)
    assert float(jnp.abs(moved - nll).max()) > 1e-4
    np.testing.assert_array_equal(np.asarray(own[0]), np.asarray(choice[0]))
    loss, got = jax.value_and_grad(
        lambda p: model.loss_fn(p, fed, None)[0])(params)
    want_loss, want = jax.value_and_grad(
        lambda p: ref.forward(p, fed, _as_dict(cfg))[0])(params)
    np.testing.assert_allclose(float(loss), float(want_loss), rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(moved),
        np.asarray(ref.forward(params, fed, _as_dict(cfg))[1]["nll"]),
        rtol=2e-5, atol=2e-6)
    for name in ("w_gate", "router"):
        np.testing.assert_allclose(
            np.asarray(got["layers"][name]),
            np.asarray(want["layers"][name]), rtol=2e-3, atol=2e-6)


def test_the_yarn_table_at_the_published_sizes(ref):
    """``low`` 18, ``high`` 35, ``a`` 1.27726 and hand-computed turns:
    pair 17 keeps its frequency, pair 36 is slowed sixteen times, pair
    26 lies 8/17 up the ramp; the sliding layers' rows are the default
    RoPE at the same theta."""
    cfg = mellum2.Mellum2Config()
    low, high, ramp = mellum2.yarn_ramp(cfg)
    assert (low, high) == (18, 35)
    np.testing.assert_allclose(ramp[[17, 18, 26, 35, 36]],
                               [0.0, 0.0, 8 / 17, 1.0, 1.0])
    tables = mellum2.rope_tables(cfg)
    w, a = np.asarray(tables["rope_w"]), np.asarray(tables["rope_a"])
    window = np.asarray(tables["is_window"])
    assert w.shape == (28, 64) and window.tolist() == [True] * 3 + [False]\
        + window.tolist()[4:]
    assert window.sum() == 21 and not window[3::4].any()
    plain = 5e5 ** (-np.arange(64) / 64)
    np.testing.assert_allclose(w[0], plain, rtol=1e-6)
    np.testing.assert_allclose(w[3, :19], plain[:19], rtol=1e-6)
    np.testing.assert_allclose(w[3, 35:], plain[35:] / 16, rtol=1e-6)
    np.testing.assert_allclose(
        w[3, 26], plain[26] * ((1 - 8 / 17) + 8 / 17 / 16), rtol=1e-6)
    assert a[0] == 1.0
    assert a[3] == pytest.approx(1.2772588722239782, rel=1e-6)
    assert a[3] == pytest.approx(0.1 * np.log(16) + 1)
    # the reference writes the same table from the same equations
    want = ref.layer_tables(_as_dict(cfg))
    np.testing.assert_allclose(w, want["rope_w"], rtol=1e-6)
    np.testing.assert_allclose(a, want["rope_a"], rtol=1e-6)
    assert (want["window"] >= cfg.seq_len).tolist() == (~window).tolist()
    assert set(want["window"][window]) == {1024}
    assert ref.yarn(_as_dict(cfg))[:2] == (18, 35)


@pytest.mark.parametrize("layer", [0, 2])
def test_a_layers_output_differs_when_its_kind_is_flipped(layer):
    """One traced body, told its kind by values: the same weights under
    the other kind's window and RoPE give another output, and under its
    own the model's."""
    cfg, _, params, batch = _setup(seed=4)
    p = jax.tree.map(lambda a: a[layer], params["layers"])
    tables = mellum2.rope_tables(cfg)
    own = jax.tree.map(lambda a: a[layer], tables)
    other_layer = 2 if layer == 0 else 0
    other = jax.tree.map(lambda a: a[other_layer], tables)
    assert bool(own["is_window"]) != bool(other["is_window"])
    h = jnp.take(params["emb"], batch["x"], axis=0)
    body = jax.jit(lambda kind: mellum2._layer(cfg, p, kind, h)[0])
    assert float(jnp.abs(body(own) - body(other)).max()) > 1e-3
    # the window alone, and the RoPE alone, each move it
    for key in ("is_window", "rope_w", "rope_a"):
        mixed = {**own, key: other[key]}
        assert float(jnp.abs(body(own) - body(mixed)).max()) > 1e-5, key


def test_the_blocks_are_one_scan_body_with_both_kinds_kernels():
    """The loss's jaxpr holds ONE scan over the six layers, and its body
    one ``cond`` between the windowed and the plain forward call."""
    cfg, model, params, batch = _setup(impls=("flash_interpret", None),
                                       flash_tiles=(8, 8))
    text = str(jax.make_jaxpr(
        lambda p: model.loss_fn(p, batch, None)[0])(params))
    assert text.count("scan[") == 1
    assert text.count("name=flash_fwd_win") == 1
    assert text.count("name=flash_fwd\n") + text.count("name=flash_fwd ") \
        == 1


def test_one_kind_of_layer_needs_no_flag():
    """All full: the plain calls, no window; all sliding: the windowed
    calls always, no ``cond`` between kinds."""
    for types, want, absent in (
            ((mellum2.FULL,), "name=flash_fwd", "flash_fwd_win"),
            ((mellum2.SLIDING,), "name=flash_fwd_win", "name=flash_fwd\n")):
        cfg, model, params, batch = _setup(
            impls=("flash_interpret", None), flash_tiles=(8, 8),
            layer_types=types, num_layers=2)
        text = str(jax.make_jaxpr(
            lambda p: model.loss_fn(p, batch, None)[0])(params))
        assert want in text and absent not in text


def test_the_four_shares_add_up_to_the_uncut_layer(ref):
    """Four chips hold 2 of 8 experts each (``first_expert`` 0, 2, 4,
    6): what their layers add to the stream sums to what the uncut
    reference's whole layer adds, the attention and the router counted
    once; on a sliding layer and on a full one."""
    cfg, _, params, batch = _setup(seed=5, experts_held=8, batch_size=4)
    B, T = batch["x"].shape
    h = jnp.take(params["emb"], batch["x"], axis=0)
    tables = mellum2.rope_tables(cfg)
    want_tables = ref.layer_tables(_as_dict(cfg))
    for layer in (0, 2):
        p = jax.tree.map(lambda a: a[layer], params["layers"])
        kind = jax.tree.map(lambda a: a[layer], tables)
        with jax.default_matmul_precision("highest"):
            whole, _, _ = ref._layer(
                _as_dict(cfg), p,
                {k: jnp.asarray(v[layer]) for k, v in want_tables.items()},
                h, None)
        after_attention, _, _ = mellum2._layer(
            cfg, {**p, "w_down": jnp.zeros_like(p["w_down"])}, kind, h)
        added, rows = 0.0, 0.0
        for first in (0, 2, 4, 6):
            share = dataclasses.replace(cfg, experts_held=2,
                                        first_expert=first)
            cut = {**p, **{k: p[k][first:first + 2]
                           for k in ("w_gate", "w_up", "w_down")}}
            out, scalars, _ = mellum2._layer(share, cut, kind, h)
            added = added + (out - after_attention)
            rows += float(scalars["moe_rows_here"])
            assert float(scalars["moe_dropped"]) == 0.0
        assert rows == B * T * cfg.experts_per_token
        np.testing.assert_allclose(np.asarray(after_attention + added),
                                   np.asarray(whole), rtol=2e-4, atol=2e-5)


def test_flop_count_of_the_cell_and_of_the_published_model(ref):
    """A sliding layer's attention counts ITS pairs (7,864,832 a head
    at 8,192 under 1,024), a full layer's the causal triangle
    (33,558,528); the head is 8.7 % of the published model's forward
    matrix work at 8k and 12.8 % of the cell's."""
    assert ref.attended_pairs(8192, 1024) == 7864832
    assert ref.attended_pairs(8192, 8192) == 33558528
    assert ref.attended_pairs(8192, 10 ** 6) == 33558528
    full = _as_dict(mellum2.Mellum2Config())
    cell = dict(full, num_layers=4, experts_held=16, vocab_size=12288)
    D, T = 2304, 8192
    proj = 2 * D * (2 * 32 * 128 + 2 * 4 * 128)
    router = 2 * D * 64
    expert = 3 * 2 * D * 896
    sliding = 2 * 2 * 32 * 128 * 7864832 / T
    causal = 2 * 2 * 32 * 128 * 33558528 / T
    layers = 4 * (proj + router + 8 * 16 / 64 * expert) + 3 * sliding \
        + causal
    head = 2 * D * 12288
    assert ref.train_matmul_flops_per_token(cell) == pytest.approx(
        3 * (layers + head), rel=1e-9)
    assert head / (layers + head) == pytest.approx(0.128, abs=0.002)
    whole = ref.train_matmul_flops_per_token(full) / 3
    assert 2 * D * 98304 / whole == pytest.approx(0.087, abs=0.002)
    # never the causal count for all four layers
    all_causal = 4 * (proj + router + 2 * expert + causal) + head
    assert ref.train_matmul_flops_per_token(cell) < 0.85 * 3 * all_causal


def test_trains_through_parallel_run_with_its_table_and_gauges():
    cfg = mellum2.tiny_config(compute_dtype=jnp.bfloat16)
    sess, *_ = parallax.parallel_run(
        mellum2.build_model(cfg),
        parallax_config=parallax.Config(
            run_option="HYBRID", sparse_grad_mode="slices",
            search_partitions=False, shape_buckets=[8]),
        seed=3)
    batch = mellum2.make_batch(np.random.default_rng(0), 8, cfg.seq_len,
                               cfg.vocab_size)
    losses = [float(sess.run("loss", feed_dict=batch)) for _ in range(12)]
    assert losses[-1] < losses[0] and all(np.isfinite(losses))
    assert sorted(sess.state.slice_state) == ["emb"]
    out = sess.run(["lm_loss", "aux_loss", "moe_dropped", "moe_rows_here"],
                   feed_dict=batch)
    assert float(out[2]) == 0.0 and float(out[3]) > 0.0
    snap = sess.metrics_snapshot()
    assert snap["moe.dropped"] == 0.0
    assert snap["moe.rows_here"] == float(out[3])
    assert snap["moe.rows_walked"] == snap["moe.rows_here"]
    assert snap["moe.load_max_over_mean"] >= 1.0
    sess.close()


@pytest.mark.parametrize("kw", [
    dict(experts_held=4, first_expert=6),
    dict(layer_types=("sliding_attention", "linear_attention")),
    dict(layer_types=(mellum2.SLIDING,) * 4),
    dict(num_heads=3),
])
def test_a_config_the_model_cannot_be_is_refused(kw):
    with pytest.raises(ValueError):
        mellum2.build_model(mellum2.tiny_config(**kw))
