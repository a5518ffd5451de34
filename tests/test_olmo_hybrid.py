"""models/olmo_hybrid against the benchmark's plain float32 reference
(benchmark/reference/olmo-hybrid-7b.py, the rule token by token) at a
tiny size with TWO periods of (linear, linear, full): loss, every
position's NLL, every gradient; the period's stacking against the layers
written one after another; one compiled body a kind of layer; what a
layer's remat keeps of the MLP; the chip's share of the heads; the model
through ``parallel_run``."""

import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import parallax_tpu as parallax
from parallax_tpu.models import olmo_hybrid as oh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def ref():
    path = os.path.join(ROOT, "benchmark", "reference", "olmo-hybrid-7b.py")
    spec = importlib.util.spec_from_file_location("olmo_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(autouse=True)
def chunks_of_16(monkeypatch):
    """The rule's own chunk (64) would hold a tiny sequence of 32 whole:
    at 16 the state crosses from chunk to chunk here too."""
    monkeypatch.setattr(oh.delta_rule, "CHUNK", 16)


def _as_dict(cfg):
    return {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}


def _setup(seed=0, batch_size=2, impls=(None, None), **kw):
    cfg = oh.tiny_config(**kw)
    model = oh.build_model(cfg, impls)
    params = model.init_fn(jax.random.PRNGKey(seed))
    # what starts at 1 moved off it, so that a missing term shows
    rng = np.random.default_rng(seed)
    for stack, names in (("linear", ("mix_norm", "mlp_norm", "o_norm")),
                         ("full", ("mix_norm", "mlp_norm", "q_norm",
                                   "k_norm"))):
        for name in names:
            shape = params[stack][name].shape
            params[stack][name] = jnp.asarray(
                1.0 + 0.2 * rng.standard_normal(shape).astype(np.float32))
    batch = {k: jnp.asarray(v) for k, v in oh.make_batch(
        rng, batch_size, cfg.seq_len, cfg.vocab_size).items()}
    return cfg, model, params, batch


@pytest.mark.parametrize("impls", [("xla", "xla"),
                                   ("flash_interpret", "interpret")])
def test_loss_nll_and_every_gradient_match_the_reference(ref, impls):
    cfg, model, params, batch = _setup(impls=impls, flash_tiles=(8, 8))
    assert cfg.periods == 2
    loss, grads = jax.value_and_grad(
        lambda p: model.loss_fn(p, batch, None)[0])(params)
    want_loss, want_grads = jax.value_and_grad(
        lambda p: ref.forward(p, batch, _as_dict(cfg))[0])(params)
    np.testing.assert_allclose(float(loss), float(want_loss), rtol=1e-5)
    flat = jax.tree_util.tree_leaves_with_path(grads)
    for (path, got), want in zip(flat, jax.tree.leaves(want_grads)):
        assert float(jnp.abs(want).max()) > 0, path
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=3e-3,
            atol=3e-5 * float(jnp.abs(want).max()),
            err_msg=jax.tree_util.keystr(path))
    nll, scalars = oh.forward(cfg, params, batch, impls)
    _, out = ref.forward(params, batch, _as_dict(cfg))
    np.testing.assert_allclose(np.asarray(nll), np.asarray(out["nll"]),
                               rtol=2e-5, atol=2e-6)
    # the gauges' two scalars are the reference's means
    assert scalars["decay_mean"].shape == (2, 2)
    np.testing.assert_allclose(float(jnp.mean(scalars["decay_mean"])),
                               float(out["decay_mean"]), rtol=1e-5)
    np.testing.assert_allclose(float(jnp.mean(scalars["beta_mean"])),
                               float(out["beta_mean"]), rtol=1e-5)


def test_the_reference_without_its_gates_is_another_model(ref):
    """The benchmark's second negative control: the decay held at 1 and
    ``beta`` without its factor 2 move every position's NLL."""
    cfg, _, params, batch = _setup(seed=1)
    m = _as_dict(cfg)
    _, own = ref.forward(params, batch, m)
    _, blind = ref.forward(params, batch, m,
                           ref.model_gates(m, without_gates=True))
    assert float(blind["decay_mean"]) == 1.0
    assert float(blind["beta_mean"]) < 0.75 * float(own["beta_mean"])
    assert float(jnp.abs(own["nll"] - blind["nll"]).mean()) > 1e-3


def test_the_periods_stacking_is_the_layers_one_after_another():
    """The scan over periods of an inner scan is what six layers written
    out give: linear layers ``[p, j]`` then full layer ``[p]``."""
    cfg, _, params, batch = _setup(seed=2)
    nll, _ = oh.forward(cfg, params, batch)
    h = jnp.take(params["emb"], batch["x"], axis=0)
    for i in range(cfg.periods):
        for j in range(len(cfg.layer_types) - 1):
            h, _ = oh.linear_layer(
                cfg, jax.tree.map(lambda a: a[i, j], params["linear"]), h)
        h = oh.full_layer(cfg, jax.tree.map(lambda a: a[i], params["full"]),
                          h)
    hidden = oh.rms_norm(h, params["final_norm"], cfg.rms_norm_eps)
    logits = hidden.reshape(-1, cfg.model_dim) @ params["head"]
    logp = jax.nn.log_softmax(logits[:, :cfg.vocab_size], axis=-1)
    want = -jnp.take_along_axis(logp, batch["y"].reshape(-1, 1), axis=1)
    np.testing.assert_allclose(np.asarray(nll).ravel(),
                               np.asarray(want)[:, 0], rtol=2e-5, atol=2e-6)


def test_one_compiled_body_a_kind_of_layer_however_deep():
    """Two periods of (linear, linear, full), six layers: the loss's
    jaxpr calls each forward kernel ONCE, under a scan (of periods) of a
    scan (of a period's linear layers)."""
    cfg, model, params, batch = _setup(
        impls=("flash_interpret", "interpret"), flash_tiles=(8, 8))
    text = str(jax.make_jaxpr(
        lambda p: model.loss_fn(p, batch, None)[0])(params))
    assert text.count("name=delta_fwd") == 1
    assert text.count("name=flash_fwd") == 1
    assert text.count("scan[") == 2


@pytest.fixture
def mlp_name_out_of_the_policy(monkeypatch):
    """Calling it takes ``MLP_KEPT`` out of every policy built from then
    on, to the test's end: the model as it was before the name."""
    names_only = jax.checkpoint_policies.save_only_these_names

    def take_out():
        monkeypatch.setattr(
            jax.checkpoint_policies, "save_only_these_names",
            lambda *names: names_only(
                *(n for n in names if n != oh.MLP_KEPT)))
    return take_out


def _mlp_products(jaxpr, D, F, under=()):
    """``(primitives above, name stack)`` of every ``dot_general`` in
    ``jaxpr`` with an operand or a result ``[.., D, F]`` or ``[.., F,
    D]``: the MLP's forward products and their two cotangents each."""
    found = []
    for eqn in jaxpr.eqns:
        shapes = [v.aval.shape[-2:] for v in (*eqn.invars, *eqn.outvars)]
        if eqn.primitive.name == "dot_general" and (
                (D, F) in shapes or (F, D) in shapes):
            found.append((under, str(eqn.source_info.name_stack)))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _mlp_products(sub, D, F, under + (eqn.primitive.name,))
    return found


def test_the_remat_makes_one_mlp_product_again(mlp_name_out_of_the_policy):
    """The gradient's jaxpr holds, in each kind of layer's bodies, ten
    products of the MLP's shapes (three forward, six backward and the
    gate's made again under the rematerialised computation); with
    ``MLP_KEPT`` out of the policy twelve, all three made again."""
    cfg, model, params, batch = _setup()
    D, F = cfg.model_dim, cfg.intermediate_size

    def by_kind():
        jaxpr = jax.make_jaxpr(jax.grad(
            lambda p: model.loss_fn(p, batch, None)[0]))(params)
        found = _mlp_products(jaxpr.jaxpr, D, F)
        assert all("mlp" in stack for _, stack in found)
        # a linear layer's body lies under the scan of a scan
        kinds = {"linear": [s for under, s in found
                            if under.count("scan") == 2],
                 "full": [s for under, s in found
                          if under.count("scan") == 1]}
        assert sum(map(len, kinds.values())) == len(found)
        return {kind: (len(stacks), sum("rematted_computation" in s
                                        for s in stacks))
                for kind, stacks in kinds.items()}

    assert by_kind() == {"linear": (10, 1), "full": (10, 1)}
    mlp_name_out_of_the_policy()
    assert by_kind() == {"linear": (12, 3), "full": (12, 3)}


@pytest.mark.parametrize("impls", [("xla", "xla"),
                                   ("flash_interpret", "interpret")])
def test_keeping_the_mlps_products_changes_no_number(
        impls, mlp_name_out_of_the_policy):
    """Loss and every gradient leaf with ``MLP_KEPT`` kept are bit for
    bit those of the model that makes all three products again."""
    cfg, model, params, batch = _setup(impls=impls, flash_tiles=(8, 8))

    def loss_and_grads():
        return jax.jit(jax.value_and_grad(
            lambda p: model.loss_fn(p, batch, None)[0]))(params)

    loss, grads = loss_and_grads()
    mlp_name_out_of_the_policy()
    want_loss, want_grads = loss_and_grads()
    assert np.array_equal(np.asarray(loss), np.asarray(want_loss))
    flat = jax.tree_util.tree_leaves_with_path(grads)
    assert len(flat) == len(jax.tree.leaves(params))
    for (path, got), want in zip(flat, jax.tree.leaves(want_grads)):
        assert float(jnp.abs(want).max()) > 0, path
        assert np.array_equal(np.asarray(got), np.asarray(want)), \
            jax.tree_util.keystr(path)


def test_the_two_head_shares_add_up_to_the_uncut_layer():
    """Two chips hold 2 of 4 heads each. A linear layer's mixer: every
    operation before ``Wo`` is a head's own (the gated norm's weight is
    one head's, shared), so the two shares' outputs add up to the uncut
    mixer's exactly. A full layer's do once each share is given the
    uncut layer's QK-norm statistic; THE CELL TAKES IT OVER THE HELD
    HALF (``full_qkv`` of a share), which is another number. The norm on
    the mixer's output (behind the deployment's all-reduce) and the MLP,
    which every chip computes alike, are counted once."""
    cfg, _, params, batch = _setup(seed=5)
    h = jnp.take(params["emb"], batch["x"], axis=0)
    share = dataclasses.replace(cfg, heads_held=2)
    halves = [(first, oh.held_share(cfg, params, first, 2))
              for first in (0, 2)]

    p = jax.tree.map(lambda a: a[0, 1], params["linear"])
    whole, _, _ = oh.linear_mixer(cfg, p, h)
    parts = [oh.linear_mixer(
        share, jax.tree.map(lambda a: a[0, 1], cut["linear"]), h)[0]
        for _, cut in halves]
    np.testing.assert_allclose(np.asarray(sum(parts)), np.asarray(whole),
                               rtol=1e-5, atol=1e-6)
    out, _ = oh.linear_layer(cfg, p, h)
    np.testing.assert_allclose(
        np.asarray(oh._block(cfg, p, h, sum(parts))), np.asarray(out),
        rtol=1e-4, atol=1e-5)

    p = jax.tree.map(lambda a: a[1], params["full"])
    whole = oh.full_mixer(cfg, p, h)
    q, k, v = oh.full_qkv(cfg, p, h)
    parts, own = [], []
    for first, cut in halves:
        p_cut = jax.tree.map(lambda a: a[1], cut["full"])
        assert p_cut["wq"].shape == (cfg.model_dim, 2 * cfg.head_dim)
        parts.append(oh.full_attend(
            share, p_cut, *(a[:, :, first:first + 2] for a in (q, k, v))))
        own.append(oh.full_mixer(share, p_cut, h))
    np.testing.assert_allclose(np.asarray(sum(parts)), np.asarray(whole),
                               rtol=1e-5, atol=1e-6)
    assert float(jnp.abs(sum(own) - whole).max()) > 1e-3


def test_flop_count_of_the_cell_and_of_the_published_model(ref):
    """The published model's matrix parameters by the count: 7.43 B with
    the table, so 6 x (7.43 B - the table's 0.385 B) a token and the
    pairs; the cell's four layers with 15 of 30 heads: 718 M."""
    full = _as_dict(oh.OlmoHybridConfig())
    D, F = 3840, 11008
    linear = D * 30 * (2 * 96 + 3 * 192 + 2) + 3 * D * F
    causal = 4 * D * 30 * 128 + 3 * D * F
    head = D * 100352
    assert 24 * linear + 8 * causal + 2 * head == pytest.approx(7.43e9,
                                                                rel=2e-3)
    T = 8192
    rule = 3 * 2 * 30 * 96 * 192
    pairs = 2 * 2 * 30 * 128 * (T + 1) / 2
    want = 3 * (2 * (24 * linear + 8 * causal + head) + 24 * rule
                + 8 * pairs)
    assert ref.train_matmul_flops_per_token(full) == pytest.approx(
        want, rel=1e-9)
    cell = dict(full, num_layers=4, num_heads=15, vocab_size=12544)
    matrices = 3 * (D * 15 * (2 * 96 + 3 * 192 + 2) + 3 * D * F) \
        + 4 * D * 15 * 128 + 3 * D * F + D * 12544
    assert matrices == pytest.approx(718e6, rel=2e-3)
    assert ref.train_matmul_flops_per_token(cell) == pytest.approx(
        3 * (2 * matrices + 3 * rule / 2 + pairs / 2), rel=1e-9)


def test_trains_through_parallel_run_with_its_table_and_gauges():
    cfg = oh.tiny_config(compute_dtype=jnp.bfloat16, heads_held=2)
    sess, *_ = parallax.parallel_run(
        oh.build_model(cfg),
        parallax_config=parallax.Config(
            run_option="HYBRID", sparse_grad_mode="slices",
            search_partitions=False, shape_buckets=[8]),
        seed=3)
    batch = oh.make_batch(np.random.default_rng(0), 8, cfg.seq_len,
                          cfg.vocab_size)
    losses = [float(sess.run("loss", feed_dict=batch)) for _ in range(12)]
    assert losses[-1] < losses[0] and all(np.isfinite(losses))
    assert sorted(sess.state.slice_state) == ["emb"]
    assert sess.state.params["linear"]["wq"].shape == (
        2, 2, cfg.model_dim, 2 * cfg.linear_key_head_dim)
    decay, beta = sess.run(["linear_decay_mean", "linear_beta_mean"],
                           feed_dict=batch)
    assert 0.0 < float(decay) < 1.0 and 0.0 < float(beta) < 2.0
    snap = sess.metrics_snapshot()
    assert snap["linear_attn.decay_mean"] == float(decay)
    assert snap["linear_attn.beta_mean"] == float(beta)
    sess.close()


@pytest.mark.parametrize("kw", [
    dict(heads_held=5),
    dict(heads_held=0),
    dict(heads_held=-1),
    dict(num_heads=0),
    dict(layer_types=(oh.FULL, oh.LINEAR)),
    dict(layer_types=(oh.LINEAR, oh.FULL), num_layers=3),
    dict(layer_types=("sliding_attention", oh.FULL)),
])
def test_a_config_the_model_cannot_be_is_refused(kw):
    with pytest.raises(ValueError):
        oh.build_model(oh.tiny_config(**kw))
