"""models/keye_vl2 against the benchmark's plain float32 reference
(benchmark/reference/keye-vl2-30b-a3b.py) at a tiny size: loss, its
three parts, every position's NLL, every gradient, what layer 0
selects; the three-stream RoPE; the chip's share of the experts; the
model through ``parallel_run``."""

import collections
import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import parallax_tpu as parallax
from parallax_tpu.models import keye_vl2 as kv

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def ref():
    path = os.path.join(ROOT, "benchmark", "reference",
                        "keye-vl2-30b-a3b.py")
    spec = importlib.util.spec_from_file_location("keye_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _as_dict(cfg):
    return {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}


def _setup(seed=0, batch_size=2, **kw):
    cfg = kv.tiny_config(**kw)
    model = kv.build_model(cfg)
    params = model.init_fn(jax.random.PRNGKey(seed))
    # norms off their initial 1, so that a missing scale would show
    rng = np.random.default_rng(seed)
    for name in ("ln1", "ln2", "q_norm", "k_norm"):
        shape = params["layers"][name].shape
        params["layers"][name] = jnp.asarray(
            1.0 + 0.2 * rng.standard_normal(shape).astype(np.float32))
    batch = {k: jnp.asarray(v) for k, v in kv.make_batch(
        rng, batch_size, cfg.seq_len, cfg.vocab_size).items()}
    return cfg, model, params, batch


def test_loss_parts_and_nll_match_the_reference(ref):
    cfg, model, params, batch = _setup()
    loss, metrics = model.loss_fn(params, batch, None)
    want_loss, want = ref.forward(params, batch, _as_dict(cfg),
                                  collect_layer=0)
    np.testing.assert_allclose(float(loss), float(want_loss), rtol=1e-5)
    for k in ("lm_loss", "aux_loss", "indexer_loss"):
        np.testing.assert_allclose(float(metrics[k]), float(want[k]),
                                   rtol=2e-5, err_msg=k)
    assert float(metrics["moe_dropped"]) == 0.0
    assert 0.3 < float(metrics["attn_selected_share"]) < 1.0
    # every position's NLL, by the derivative in the weights (what the
    # benchmark's builder reads)
    g_w = jax.grad(lambda w: model.loss_fn(
        params, {**batch, "w": w}, None)[0])(batch["w"])
    nll = metrics["lm_loss"] + g_w * jnp.sum(batch["w"])
    np.testing.assert_allclose(np.asarray(nll), np.asarray(want["nll"]),
                               rtol=1e-4, atol=1e-4)
    picked = kv.layer_selection(cfg, params, batch, 0)
    np.testing.assert_array_equal(np.asarray(picked["selection"]),
                                  np.asarray(want["selection"]))
    np.testing.assert_array_equal(np.asarray(picked["expert_choice"]),
                                  np.asarray(want["expert_choice"]))


def test_every_gradient_matches_the_reference(ref):
    cfg, model, params, batch = _setup(seed=1)
    got = jax.grad(lambda p: model.loss_fn(p, batch, None)[0])(params)
    want = jax.grad(lambda p: ref.forward(p, batch, _as_dict(cfg))[0])(
        params)
    flat_got = jax.tree_util.tree_leaves_with_path(got)
    flat_want = jax.tree_util.tree_leaves(want)
    assert len(flat_got) == 18
    for (path, g), w in zip(flat_got, flat_want):
        scale = float(jnp.abs(w).max())
        assert scale > 0, path        # every array is reached
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=5e-4, atol=5e-5 * scale,
                                   err_msg=str(path))
    # the benchmark's cut of it
    _, cut = ref.loss_and_grads(params, batch, _as_dict(cfg))
    for k in ref.GRAD_ARRAYS:
        np.testing.assert_allclose(np.asarray(cut[k]),
                                   np.asarray(want["layers"][k]),
                                   rtol=1e-5, atol=1e-8)


def _scanned_loss(cfg, params, batch):
    """The model's loss with the blocks under a ``lax.scan`` over the
    float32 stacks as they are, each under the model's own
    ``jax.checkpoint``: the reference of the one test below. ``(loss,
    per-layer scalars)``."""
    B, T = batch["x"].shape
    pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None, :, None],
                           (B, T, 3))
    body = jax.checkpoint(
        lambda h, p: kv._layer(cfg, p, h, pos)[:2],
        policy=jax.checkpoint_policies.save_only_these_names(
            "sparse_attn_chunk", "moe_rows"))
    h, per_layer = jax.lax.scan(
        body, jnp.take(params["emb"], batch["x"], axis=0), params["layers"])
    hidden = kv.rms_norm(h, params["final_norm"], cfg.rms_norm_eps)
    logits = hidden.reshape(B * T, -1) @ params["head"]
    logits = jnp.where(jnp.arange(logits.shape[-1]) < cfg.vocab_size,
                       logits, -jnp.inf)
    nll = -jnp.take_along_axis(jax.nn.log_softmax(logits),
                               batch["y"].reshape(B * T, 1), axis=-1)
    w = batch["w"].reshape(B * T)
    lm_loss = jnp.sum(nll[:, 0] * w) / jnp.sum(w)
    loss = (lm_loss
            + cfg.router_aux_loss_coef * jnp.mean(per_layer["aux_loss"])
            + cfg.indexer_loss_weight
            * jnp.mean(per_layer["indexer_loss"]) / (B * T))
    return loss, {**per_layer, "lm_loss": lm_loss}


def test_the_blocks_equal_a_plain_scan_and_the_stacks_keep_their_layout():
    """Loss, its parts, what the gauges read and every leaf's gradient
    of ``loss_fn`` against the same ``_layer`` under a ``lax.scan``, to
    float32 round-off; ``params["layers"]`` and its gradient stay
    stacked ``[L, ...]`` leaf by leaf."""
    cfg, model, params, batch = _setup(seed=5, num_layers=3)
    L, (B, T) = cfg.num_layers, batch["x"].shape
    (loss, metrics), grads = jax.jit(jax.value_and_grad(
        lambda p: model.loss_fn(p, batch, None), has_aux=True))(params)
    (want, s), want_grads = jax.jit(jax.value_and_grad(
        lambda p: _scanned_loss(cfg, p, batch), has_aux=True))(params)
    np.testing.assert_allclose(float(loss), float(want), rtol=1e-6)
    for key, read in {
            "lm_loss": s["lm_loss"], "aux_loss": jnp.mean(s["aux_loss"]),
            "indexer_loss": jnp.mean(s["indexer_loss"]) / (B * T),
            "moe_dropped": jnp.max(s["moe_dropped"]),
            "moe_rows_here": jnp.mean(s["moe_rows_here"]),
            "moe_rows_walked": jnp.mean(s["moe_rows_walked"]),
            "moe_load_max_over_mean": jnp.mean(s["moe_load_max_over_mean"]),
            "attn_selected_share":
                jnp.sum(s["selected"]) / jnp.sum(s["causal"])}.items():
        np.testing.assert_allclose(float(metrics[key]), float(read),
                                   rtol=1e-6, err_msg=key)
    assert set(metrics) == {"lm_loss", "aux_loss"} | {
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
    cfg, model, params, batch = _setup(compute_dtype=jnp.bfloat16)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda p: model.loss_fn(p, batch, None)[0]))(params)
    backward, = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"
                 and e.params["reverse"]]
    stacks = backward.outvars[backward.params["num_carry"]:]
    want = collections.Counter(
        (leaf.shape, "bfloat16" if name in kv.MATRICES else "float32")
        for name, leaf in params["layers"].items())
    assert collections.Counter((v.aval.shape, str(v.aval.dtype))
                               for v in stacks) == want


def test_indexer_learns_from_its_loss_alone(ref):
    cfg, model, params, batch = _setup(seed=2)
    quiet = dataclasses.replace(cfg, indexer_loss_weight=0.0)
    g = jax.grad(lambda p: kv.build_model(quiet).loss_fn(
        p, batch, None)[0])(params)
    for k in ("idx_wq", "idx_wk", "idx_ww"):
        assert float(jnp.abs(g["layers"][k]).max()) == 0.0
    assert float(jnp.abs(g["layers"]["wq"]).max()) > 0.0


def test_rope_in_three_streams(ref):
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((2, 6, 3, 16)).astype(np.float32))
    t = jnp.arange(6)
    same = jnp.broadcast_to(t[None, :, None], (2, 6, 3))
    section = (2, 2, 4)
    # equal streams: plain half-split RoPE
    n = 8
    angle = t[:, None] * (1e4 ** (-jnp.arange(n) / n))[None, :]
    cos, sin = jnp.cos(angle)[None, :, None], jnp.sin(angle)[None, :, None]
    plain = jnp.concatenate([x[..., :n] * cos - x[..., n:] * sin,
                             x[..., n:] * cos + x[..., :n] * sin], -1)
    np.testing.assert_allclose(np.asarray(kv.rope3(x, same, 1e4, section)),
                               np.asarray(plain), rtol=1e-5, atol=1e-6)
    # unequal streams: the reference's, pair by pair
    pos = jnp.asarray(rng.integers(0, 50, (2, 6, 3)))
    got = kv.rope3(x, pos, 1e4, section)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref.rope3(x, pos, 1e4, section)),
        rtol=1e-5, atol=1e-6)
    assert not np.allclose(np.asarray(got), np.asarray(
        kv.rope3(x, jnp.broadcast_to(pos[..., :1], pos.shape), 1e4,
                 section)))
    # a key without a head axis, half the pairs: sections scale
    key = x[:, :, 0, :8]
    np.testing.assert_allclose(
        np.asarray(kv.rope3(key, pos, 1e4, section)),
        np.asarray(ref.rope3(key, pos, 1e4, section)), rtol=1e-5, atol=1e-6)
    with pytest.raises(ValueError, match="scale"):
        kv.rope3(x[..., :6], pos, 1e4, (2, 3, 3))


def test_positions_reach_the_model(ref):
    cfg, model, params, batch = _setup(seed=4)
    B, T = batch["x"].shape
    pos = jnp.asarray(np.random.default_rng(4).integers(0, 40, (B, T, 3)))
    moved = {**batch, "pos": pos}
    got = model.loss_fn(params, moved, None)[0]
    want = ref.forward(params, moved, _as_dict(cfg))[0]
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)
    assert abs(float(got) - float(model.loss_fn(params, batch, None)[0])) \
        > 1e-4


def test_the_eight_shares_add_up_to_the_whole_layer(ref):
    """Eight chips hold two of sixteen experts each (first_expert 0, 2,
    ..., 14): what their layers add to the stream sums to what the
    uncut reference's whole layer adds, the attention and the router
    counted once."""
    cfg, _, params, batch = _setup(seed=5, num_experts=16, experts_held=16,
                                   experts_per_token=4, num_layers=1)
    p = jax.tree.map(lambda a: a[0], params["layers"])
    B, T = batch["x"].shape
    pos = jnp.broadcast_to(jnp.arange(T)[None, :, None], (B, T, 3))
    h = jnp.take(params["emb"], batch["x"], axis=0)
    with jax.default_matmul_precision("highest"):
        whole, *_ = ref._layer(ref._fields(_as_dict(cfg)), p, h, pos, False)
    after_attention = kv._layer(
        cfg, {**p, "w_down": jnp.zeros_like(p["w_down"])}, h, pos)[0]
    added, rows = 0.0, 0.0
    for first in range(0, 16, 2):
        share = dataclasses.replace(cfg, experts_held=2, first_expert=first)
        cut = {**p, **{k: p[k][first:first + 2]
                       for k in ("w_gate", "w_up", "w_down")}}
        out, scalars, _ = kv._layer(share, cut, h, pos)
        added = added + (out - after_attention)
        rows += float(scalars["moe_rows_here"])
        assert float(scalars["moe_dropped"]) == 0.0
    assert rows == B * T * 4
    np.testing.assert_allclose(np.asarray(after_attention + added),
                               np.asarray(whole), rtol=2e-4, atol=2e-5)


def test_flop_count_of_the_published_model(ref):
    """The issue's count: 437 MFLOP forward, 1.31 GFLOP a trained token
    at the cell's sizes."""
    cell = dict(_as_dict(kv.KeyeVL2Config()), num_layers=4, experts_held=16,
                vocab_size=18992)
    flops = ref.train_matmul_flops_per_token(cell)
    assert flops == pytest.approx(1.313e9, rel=2e-3)
    assert ref.train_matmul_flops_per_token(
        dict(cell, indexer_topk=8192)) > flops


def test_trains_through_parallel_run_and_reports_its_outputs():
    cfg = kv.tiny_config(compute_dtype=jnp.bfloat16)
    sess, *_ = parallax.parallel_run(
        kv.build_model(cfg),
        parallax_config=parallax.Config(
            run_option="HYBRID", sparse_grad_mode="slices",
            search_partitions=False, shape_buckets=[8]),
        seed=3)
    batch = kv.make_batch(np.random.default_rng(0), 8, cfg.seq_len,
                          cfg.vocab_size)
    losses = [float(sess.run("loss", feed_dict=batch)) for _ in range(12)]
    assert losses[-1] < losses[0] and all(np.isfinite(losses))
    out = sess.run(["lm_loss", "indexer_loss", "moe_dropped",
                    "moe_rows_here", "moe_load_max_over_mean",
                    "attn_selected_share"], feed_dict=batch)
    assert float(out[2]) == 0.0 and float(out[3]) > 0.0
    snap = sess.metrics_snapshot()
    assert snap["moe.dropped"] == 0.0
    assert snap["moe.rows_here"] == float(out[3])
    # off the TPU the per-token sums visit the live rows and no other
    assert snap["moe.rows_walked"] == snap["moe.rows_here"]
    assert snap["moe.load_max_over_mean"] >= 1.0
    assert 0.0 < snap["sparse_attn.selected_share"] <= 1.0
    assert snap["sparse_attn.indexer_loss"] >= 0.0
    assert sorted(sess.state.slice_state) == ["emb"]
    sess.close()


def test_experts_outside_the_router_are_refused():
    with pytest.raises(ValueError, match="router"):
        kv.build_model(kv.tiny_config(first_expert=6, experts_held=4))


def test_the_model_declares_its_gauges_and_the_session_polls_them():
    """The session knows no output's name: it shows what ``Model.gauges``
    declares, a "max" gauge as the largest value any step gave."""
    from parallax_tpu.core.engine import Model

    model = kv.build_model(kv.tiny_config())
    assert model.gauges["moe.dropped"] == ("moe_dropped", "max")
    assert model.gauges["moe.rows_here"] == ("moe_rows_here", "last")
    with pytest.raises(ValueError, match="mode"):
        Model(model.init_fn, model.loss_fn, gauges={"x": ("y", "mean")})

    def loss_fn(params, batch):
        loss = jnp.mean((batch["x"] * params["w"]) ** 2)
        return loss, {"peak": jnp.max(batch["x"]), "now": jnp.max(batch["x"])}

    sess, *_ = parallax.parallel_run(
        Model(lambda rng: {"w": jnp.ones(())}, loss_fn,
              gauges={"demo.peak": ("peak", "max"), "demo.now": "now"}),
        parallax_config=parallax.Config(run_option="AR",
                                        search_partitions=False),
        seed=0)
    for top in (1.0, 5.0, 2.0):
        sess.run("loss", feed_dict={"x": np.full((8,), top, np.float32)})
    snap = sess.metrics_snapshot()
    assert snap["demo.peak"] == 5.0 and snap["demo.now"] == 2.0
    sess.close()


def test_the_builders_router_is_128_distinct_correlated_columns():
    """benchmark/builders/keye_train.router_in_copies: no two columns
    equal, every range a noisy permutation of the first, the scale kept;
    the top choices hold distinct experts with unequal gates and spread
    over the ranges."""
    path = os.path.join(ROOT, "benchmark", "builders", "keye_train.py")
    spec = importlib.util.spec_from_file_location("keye_train", path)
    builder = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(builder)
    cfg = kv.tiny_config(num_experts=32, experts_held=8, experts_per_token=4,
                         model_dim=64, head_dim=16)
    model = kv.build_model(cfg)
    plain = model.init_fn(jax.random.PRNGKey(0))
    params = builder.router_in_copies(model.init_fn, 4, 0.15)(
        jax.random.PRNGKey(0))
    router = np.asarray(params["layers"]["router"])            # [L, D, E]
    for k in plain:
        if k != "layers":
            np.testing.assert_array_equal(np.asarray(params[k]),
                                          np.asarray(plain[k]))
    assert len({col.tobytes() for col in router[0].T}) == 32
    np.testing.assert_allclose(router.std(), np.asarray(
        plain["layers"]["router"]).std(), rtol=0.1)
    # each column of a later range lies along one column of the first
    first = router[0, :, :8] / np.linalg.norm(router[0, :, :8], axis=0)
    later = router[0, :, 8:] / np.linalg.norm(router[0, :, 8:], axis=0)
    cos = first.T @ later                                       # [8, 24]
    assert np.all(cos.max(axis=0) > 0.9)
    assert sorted(cos[:, :8].argmax(axis=0)) == list(range(8))
    y = np.random.default_rng(0).standard_normal((512, 64)).astype(np.float32)
    logits = y @ router[0]
    top = np.argsort(-logits, axis=1)[:, :4]
    assert np.mean([len({e // 8 for e in row}) for row in top]) > 3.0
    gates = np.take_along_axis(logits, top, axis=1)
    assert np.all(gates[:, 0] > gates[:, -1])
