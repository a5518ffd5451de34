"""The layers' names inside the compiled step: the
``jax.named_scope``s of ``obs/xprof.LAYER_SCOPES`` (twenty) as
``Engine.layer_index()`` reads them back off the executable, the one
rule for reading a scope (``xprof.layer_of`` / ``sparse_split``), and
the compile cache's key, which must hold the names
(``compile/cache.ensure_persistent_cache``) or a cached executable
answers with the previous source's."""

import json
import os
import re
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import parallax_tpu as parallax
from parallax_tpu.models import lm1b
from parallax_tpu.obs import xprof

NDEV = 8
# the scopes each model's step declares, in LAYER_SCOPES' order
LM1B_SCOPES = ["embedding", "lstm", "sampled_softmax", "dense_update",
               "table_update"]
KEYE_SCOPES = ["embedding", "layer_scan", "attention", "indexer", "moe",
               "lm_head", "dense_update", "table_update"]
# no `table_update`: its tied table is the dense group's
ZAYA_SCOPES = ["embedding", "layer_scan", "attention", "cca_mix", "moe",
               "router", "lm_head", "dense_update"]
# the windowed flash calls' scope is ops/pallas_attention's own
MELLUM2_SCOPES = ["embedding", "layer_scan", "attention", "window_attention",
                  "moe", "lm_head", "dense_update", "table_update"]
# two kinds of layer with weights of their own under a scan of periods
OLMO_SCOPES = ["embedding", "layer_scan", "attention", "linear_attention",
               "delta_rule", "mlp", "lm_head", "dense_update",
               "table_update"]
# a dense layer before the loop, the gate inside `attention`, the router
# and the shared expert inside `moe`
TRINITY_SCOPES = ["embedding", "layer_scan", "attention", "window_attention",
                  "attn_gate", "mlp", "moe", "router", "shared_expert",
                  "lm_head", "dense_update", "table_update"]
# the latent paths inside `attention`, the MTP block's input by `mtp`
GLM_SCOPES = ["embedding", "layer_scan", "attention", "mla_latent", "mlp",
              "moe", "router", "shared_expert", "mtp", "lm_head",
              "dense_update", "table_update"]


def _session(**cfg_kw):
    cfg = lm1b.tiny_config(num_partitions=NDEV,
                           sparse_grad_mode="slices", **cfg_kw)
    sess, *_ = parallax.parallel_run(
        lm1b.build_model(cfg),
        parallax_config=parallax.Config(
            run_option="HYBRID", search_partitions=False,
            sparse_grad_mode="slices", shape_buckets="auto"))
    batch = lm1b.make_batch(np.random.default_rng(0), 2 * NDEV, 6,
                            cfg.vocab_size)
    return cfg, sess, batch


@pytest.fixture(scope="module")
def warmed():
    """Tiny LM1B through ``parallel_run`` on the eight virtual devices,
    slices on, warmed up, one step run, CLOSED: ``(cfg, session, the
    index it gave before close, the executable's text)``."""
    cfg, sess, batch = _session(lstm_impl="pallas", keep_prob=0.9)
    sess.warmup(feed_dict=batch)
    sess.run("loss", feed_dict=batch)
    index = sess.layer_index()
    text = next(iter(sess.engine._executables.values())).as_text()
    sess.close()
    return cfg, sess, index, text


def _ops_of(index, layer_part):
    return {name: meta for name, meta in index["hlo_index"].items()
            if layer_part in (meta.get("op_name") or "")}


def test_every_declared_scope_is_found(warmed):
    _, _, index, _ = warmed
    # the LM1B step's own scopes, and no other model's
    assert index["scopes_found"] == LM1B_SCOPES
    assert [s for s in xprof.LAYER_SCOPES if s in LM1B_SCOPES] \
        == LM1B_SCOPES
    assert index["module"] == "jit_train_step"
    assert set(index["layers"]) == set(index["hlo_index"])
    assert set(index["layers"].values()) <= set(xprof.LAYER_SCOPES) | {None}


def test_every_declared_scope_is_found_in_the_keye_step():
    """The twin case: the Keye-VL-2.0 language model's step holds its
    eight scopes; the indexer's operations, traced inside the
    attention's scope, go by their own, and a block's by theirs inside
    the scan's (innermost wins)."""
    from parallax_tpu.models import keye_vl2
    cfg = keye_vl2.tiny_config()
    sess, *_ = parallax.parallel_run(
        keye_vl2.build_model(cfg),
        parallax_config=parallax.Config(
            run_option="HYBRID", search_partitions=False,
            sparse_grad_mode="slices", shape_buckets=[8]))
    batch = keye_vl2.make_batch(np.random.default_rng(0), 8, cfg.seq_len,
                                cfg.vocab_size)
    sess.warmup(feed_dict=batch)
    index = sess.layer_index()
    sess.close()
    assert index["scopes_found"] == KEYE_SCOPES
    assert [s for s in xprof.LAYER_SCOPES if s in KEYE_SCOPES] == KEYE_SCOPES
    assert set(LM1B_SCOPES) | set(KEYE_SCOPES) | set(ZAYA_SCOPES) \
        | set(MELLUM2_SCOPES) | set(OLMO_SCOPES) | set(TRINITY_SCOPES) \
        | set(GLM_SCOPES) == set(xprof.LAYER_SCOPES)
    inner = {n: m for n, m in index["hlo_index"].items()
             if re.search(r"attention\)*/(.*/)?indexer", m.get("op_name", ""))}
    assert inner
    assert {index["layers"][n] for n in inner} == {"indexer"}
    # the scan's own operations: under its scope and no block's
    scan = [m["op_name"] for n, m in index["hlo_index"].items()
            if index["layers"][n] == "layer_scan"]
    assert scan
    assert not any(re.search(r"/(attention|indexer|moe)\)*(/|$)", o)
                   for o in scan)


@pytest.fixture(scope="module")
def zaya_index():
    from parallax_tpu.models import zaya
    cfg = zaya.tiny_config()
    sess, *_ = parallax.parallel_run(
        zaya.build_model(cfg),
        parallax_config=parallax.Config(
            run_option="HYBRID", search_partitions=False,
            shape_buckets=[8]))
    batch = zaya.make_batch(np.random.default_rng(0), 8, cfg.seq_len,
                            cfg.vocab_size)
    sess.warmup(feed_dict=batch)
    index = sess.layer_index()
    index["text"] = next(iter(sess.engine._executables.values())).as_text()
    sess.close()
    return index


def test_every_declared_scope_is_found_in_the_zaya_step(zaya_index):
    """ZAYA1-8B's step holds its eight scopes, in ``LAYER_SCOPES``'
    order, and no ``table_update``: nothing of it rides the slices
    path."""
    assert zaya_index["scopes_found"] == ZAYA_SCOPES
    assert [s for s in xprof.LAYER_SCOPES if s in ZAYA_SCOPES] == ZAYA_SCOPES


@pytest.mark.parametrize("outer,inner", [("attention", "cca_mix"),
                                         ("moe", "router")])
def test_the_inner_scope_wins_in_the_zaya_step(zaya_index, outer, inner):
    """``cca_mix`` is traced inside ``attention`` and ``router`` inside
    ``moe``: their operations go by the inner name, forward and
    backward, and the outer scope keeps operations of its own."""
    nested = {n: m for n, m in zaya_index["hlo_index"].items()
              if re.search(rf"{outer}\)*/(.*/)?{inner}",
                           m.get("op_name", ""))}
    assert nested
    assert {zaya_index["layers"][n] for n in nested} == {inner}
    assert any("transpose(" in m["op_name"] for m in nested.values())
    own = [n for n, layer in zaya_index["layers"].items() if layer == outer]
    assert own


def test_the_scans_second_carry_lands_under_layer_scan(zaya_index):
    """The router's state ``r`` rides the scan's carry beside the
    stream: its zeros, and what the loop does with the carry, sit under
    ``layer_scan``, not under no scope."""
    scan = {n: m for n, m in zaya_index["hlo_index"].items()
            if zaya_index["layers"][n] == "layer_scan"}
    assert scan
    assert not any(re.search(r"/(attention|cca_mix|moe|router)\)*(/|$)",
                             m["op_name"]) for m in scan.values())
    # the carry as each of the two layers received it, kept for the
    # backward pass in a stack the loop writes: [layers, tokens a device
    # (8 x 16 over 8), router_hidden_size]
    kept = re.findall(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = f32\[2,16,8\]\S* "
                      r"dynamic-update-slice\(", zaya_index["text"], re.M)
    assert kept
    assert {zaya_index["layers"][n] for n in kept} == {"layer_scan"}


@pytest.fixture(scope="module")
def mellum2_index():
    from parallax_tpu.models import mellum2
    cfg = mellum2.tiny_config(flash_tiles=(8, 8))
    sess, *_ = parallax.parallel_run(
        mellum2.build_model(cfg, impls=("flash_interpret", None)),
        parallax_config=parallax.Config(
            run_option="HYBRID", sparse_grad_mode="slices",
            search_partitions=False, shape_buckets=[8]))
    batch = mellum2.make_batch(np.random.default_rng(0), 8, cfg.seq_len,
                               cfg.vocab_size)
    sess.warmup(feed_dict=batch)
    index = sess.layer_index()
    sess.close()
    return index


def test_every_declared_scope_is_found_in_the_mellum2_step(mellum2_index):
    """Mellum2's step holds its eight scopes, in ``LAYER_SCOPES``'
    order: the windowed calls' among them, and no model's own inner
    scope."""
    assert mellum2_index["scopes_found"] == MELLUM2_SCOPES
    assert [s for s in xprof.LAYER_SCOPES if s in MELLUM2_SCOPES] \
        == MELLUM2_SCOPES


@pytest.mark.parametrize("direction", ["forward", "backward"])
def test_window_attention_resolves_inside_attention(mellum2_index,
                                                    direction):
    """The windowed branch of the ``cond`` between the two kinds'
    kernels is traced inside ``attention``, in both passes: its
    operations go by the inner name, the full branch's stay
    ``attention``'s."""
    def of(direction, meta):
        return ("transpose(" in meta.get("op_name", "")) \
            == (direction == "backward")

    index = mellum2_index
    nested = {n: m for n, m in index["hlo_index"].items()
              if re.search(r"attention\)*/(.*/)?window_attention",
                           m.get("op_name", "")) and of(direction, m)}
    assert nested
    assert {index["layers"][n] for n in nested} == {"window_attention"}
    # the other branch of the same `cond`: under `attention`, not under
    # the window's scope
    full = [n for n, m in index["hlo_index"].items()
            if re.search(r"attention\)*/cond/", m.get("op_name", ""))
            and "window_attention" not in m["op_name"] and of(direction, m)]
    assert full
    assert {index["layers"][n] for n in full} == {"attention"}


@pytest.fixture(scope="module")
def olmo_index():
    from parallax_tpu.models import olmo_hybrid
    cfg = olmo_hybrid.tiny_config(flash_tiles=(8, 8), heads_held=2)
    sess, *_ = parallax.parallel_run(
        olmo_hybrid.build_model(cfg, impls=("flash_interpret", "interpret")),
        parallax_config=parallax.Config(
            run_option="HYBRID", sparse_grad_mode="slices",
            search_partitions=False, shape_buckets=[8]))
    batch = olmo_hybrid.make_batch(np.random.default_rng(0), 8, cfg.seq_len,
                                   cfg.vocab_size)
    sess.warmup(feed_dict=batch)
    index = sess.layer_index()
    sess.close()
    return index


def test_sixteen_scopes_and_the_olmo_step_declares_its_own(olmo_index):
    """``LAYER_SCOPES`` holds twenty names; Olmo-Hybrid's step holds
    its nine in their order, no MoE model's among them."""
    assert len(xprof.LAYER_SCOPES) == len(set(xprof.LAYER_SCOPES)) == 20
    assert olmo_index["scopes_found"] == OLMO_SCOPES
    assert [s for s in xprof.LAYER_SCOPES if s in OLMO_SCOPES] \
        == OLMO_SCOPES


@pytest.mark.parametrize("direction", ["forward", "backward"])
def test_the_rule_resolves_inside_linear_attention(olmo_index, direction):
    """``delta_rule`` is traced inside ``linear_attention`` in both
    passes and wins there: the rule's kernel calls go by it, the
    projections, convolutions and gates around it stay the outer
    scope's; ``mlp`` stands beside them under ``layer_scan``."""
    def of(meta):
        return ("transpose(" in meta.get("op_name", "")) \
            == (direction == "backward")

    index = olmo_index
    nested = {n: m for n, m in index["hlo_index"].items()
              if re.search(r"linear_attention\)*/(.*/)?delta_rule",
                           m.get("op_name", "")) and of(m)}
    assert nested
    assert {index["layers"][n] for n in nested} == {"delta_rule"}
    kernel = "delta_bwd" if direction == "backward" else "delta_fwd"
    assert any(kernel in m["op_name"] for m in nested.values())
    outer = [n for n, m in index["hlo_index"].items()
             if re.search(r"linear_attention\)*(/|$)", m.get("op_name", ""))
             and "delta_rule" not in m["op_name"] and of(m)]
    assert outer
    assert {index["layers"][n] for n in outer} == {"linear_attention"}
    assert "mlp" in {index["layers"][n] for n, m in
                     index["hlo_index"].items() if of(m)}


@pytest.fixture(scope="module")
def trinity_index():
    from parallax_tpu.models import trinity
    cfg = trinity.tiny_config(flash_tiles=(8, 8))
    sess, *_ = parallax.parallel_run(
        trinity.build_model(cfg, impls=("flash_interpret", None)),
        parallax_config=parallax.Config(
            run_option="HYBRID", sparse_grad_mode="slices",
            search_partitions=False, shape_buckets=[8]))
    batch = trinity.make_batch(np.random.default_rng(0), 8, cfg.seq_len,
                               cfg.vocab_size)
    sess.warmup(feed_dict=batch)
    index = sess.layer_index()
    sess.close()
    return index


def test_every_declared_scope_is_found_in_the_trinity_step(trinity_index):
    """Trinity-Mini's step holds its twelve scopes in ``LAYER_SCOPES``'
    order: a dense MLP and the experts in ONE step, the gate and the
    shared expert by names of their own."""
    assert trinity_index["scopes_found"] == TRINITY_SCOPES
    assert [s for s in xprof.LAYER_SCOPES if s in TRINITY_SCOPES] \
        == TRINITY_SCOPES


@pytest.mark.parametrize("direction", ["forward", "backward"])
@pytest.mark.parametrize("outer,inner", [("attention", "attn_gate"),
                                         ("moe", "shared_expert"),
                                         ("moe", "router")])
def test_the_inner_scope_wins_in_the_trinity_step(trinity_index, outer,
                                                  inner, direction):
    """``attn_gate`` is traced inside ``attention``, ``shared_expert``
    and ``router`` inside ``moe``, in both passes: their operations go
    by the inner name, and the outer scope keeps operations of its own
    (so ``attention_ms_per_step`` and ``moe_ms_per_step`` read the rest
    of their blocks)."""
    def of(meta):
        return ("transpose(" in meta.get("op_name", "")) \
            == (direction == "backward")

    index = trinity_index
    nested = {n: m for n, m in index["hlo_index"].items()
              if re.search(rf"{outer}\)*/(.*/)?{inner}",
                           m.get("op_name", "")) and of(m)}
    assert nested
    assert {index["layers"][n] for n in nested} == {inner}
    own = [n for n, m in index["hlo_index"].items()
           if index["layers"][n] == outer and of(m)]
    assert own
    # the gate's and the shared expert's products are among them
    if inner != "router":
        assert any(m["opcode"] in ("dot", "fusion", "convolution")
                   for m in nested.values())


def test_the_biases_update_goes_by_the_router(trinity_index):
    """``ops/moe.balance_step`` runs outside the layers' loop, under its
    own ``moe`` / ``router`` scopes: ``router_ms_per_step`` holds the
    rule's update as ZAYA's does."""
    index = trinity_index
    rule = [n for n, m in index["hlo_index"].items()
            if re.search(r"train_step\)/moe/router/", m.get("op_name", ""))]
    assert rule
    assert {index["layers"][n] for n in rule} == {"router"}


@pytest.fixture(scope="module")
def glm_index():
    from parallax_tpu.models import glm4_moe_lite as glm
    cfg = glm.tiny_config(flash_tiles=(8, 8))
    sess, *_ = parallax.parallel_run(
        glm.build_model(cfg, impls=("flash_interpret", None)),
        parallax_config=parallax.Config(
            run_option="HYBRID", sparse_grad_mode="slices",
            search_partitions=False, shape_buckets=[8]))
    batch = glm.make_batch(np.random.default_rng(0), 8, cfg.seq_len,
                           cfg.vocab_size)
    sess.warmup(feed_dict=batch)
    index = sess.layer_index()
    sess.close()
    return index


def test_every_declared_scope_is_found_in_the_glm_step(glm_index):
    """GLM-4.7-Flash's step holds its twelve scopes in ``LAYER_SCOPES``'
    order: the latent paths and the MTP block's input by names of their
    own."""
    assert glm_index["scopes_found"] == GLM_SCOPES
    assert [s for s in xprof.LAYER_SCOPES if s in GLM_SCOPES] == GLM_SCOPES


@pytest.mark.parametrize("direction", ["forward", "backward"])
@pytest.mark.parametrize("outer,inner", [("attention", "mla_latent"),
                                         ("layer_scan", "mtp")])
def test_the_inner_scope_wins_in_the_glm_step(glm_index, outer, inner,
                                              direction):
    """``mla_latent`` is traced inside ``attention`` and ``mtp`` inside
    ``layer_scan``, in both passes: their products go by the inner name,
    and the outer scope keeps operations of its own (the flash kernels
    and ``Wo`` stay ``attention``'s)."""
    def of(meta):
        return ("transpose(" in meta.get("op_name", "")) \
            == (direction == "backward")

    index = glm_index
    nested = {n: m for n, m in index["hlo_index"].items()
              if re.search(rf"{outer}\)*/(.*/)?{inner}",
                           m.get("op_name", "")) and of(m)}
    assert nested
    assert {index["layers"][n] for n in nested} == {inner}
    assert any(m["opcode"] in ("dot", "fusion", "convolution")
               for m in nested.values())
    own = [n for n, m in index["hlo_index"].items()
           if index["layers"][n] == outer and of(m)]
    assert own


def test_table_scatter_maps_to_table_update(warmed):
    cfg, _, index, text = warmed
    rows = cfg.padded_vocab // NDEV
    table = re.compile(
        r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = "
        + re.escape(f"f32[{rows},{cfg.emb_dim}]") + r"\S* scatter\(",
        re.M)
    scatters = table.findall(text)
    assert scatters, "no scatter into a table's shard in the step"
    for name in scatters:
        assert index["hlo_index"][name]["opcode"] == "scatter"
        assert index["layers"][name] == "table_update", name
    # and no scatter of the step is anybody else's but the lookups'
    # gradient rows
    others = {index["layers"][n] for n, m in index["hlo_index"].items()
              if m["opcode"] == "scatter"} - {"table_update"}
    assert others <= {"embedding"}, others


def test_backward_rule_inherits_its_layer(warmed):
    _, _, index, _ = warmed
    bwd = {n: m for n, m in
           _ops_of(index, "transpose(jvp(sampled_softmax))/").items()
           if "embedding" not in m["op_name"]}
    assert bwd
    assert {index["layers"][n] for n in bwd} == {"sampled_softmax"}
    for n, m in _ops_of(index, "transpose(jvp(lstm))/").items():
        assert index["layers"][n] == "lstm", m


def test_candidate_rows_are_embeddings_innermost_wins(warmed):
    _, _, index, _ = warmed
    inner = {n: m for n, m in _ops_of(index, "sampled_softmax").items()
             if re.search(r"sampled_softmax\)*/(.*/)?embedding/",
                          m["op_name"])}
    assert any(m["opcode"] == "gather" for m in inner.values()), \
        sorted(m["op_name"] for m in inner.values())[:5]
    assert {index["layers"][n] for n in inner} == {"embedding"}


def test_exchange_under_shard_map_is_scoped(warmed):
    """Every collective of the step but the loss's mean sits under a
    layer: the lookups' exchange is ``embedding``'s."""
    _, _, index, _ = warmed
    collectives = {n: m for n, m in index["hlo_index"].items()
                   if m["opcode"] in ("all-gather", "reduce-scatter",
                                      "all-to-all", "collective-permute")}
    assert collectives
    bare = {n: m.get("op_name") for n, m in collectives.items()
            if index["layers"][n] is None}
    assert not bare, bare
    assert any(index["layers"][n] == "embedding"
               and "shard_map" in m["op_name"]
               for n, m in collectives.items())


def test_index_outlives_close_and_is_built_once(warmed):
    _, sess, index, _ = warmed
    assert sess.layer_index() is index
    assert sess.engine.layer_index() is index


def test_no_aot_executable_no_index_and_no_compile():
    """Nothing is lowered or compiled for a read: without ``warmup()``
    there is no AOT executable and the answer is None."""
    _, sess, batch = _session()
    try:
        assert sess.layer_index() is None       # no engine yet
        sess.run("loss", feed_dict=batch)
        before = sess.metrics_snapshot().get("engine.recompiles", 0)
        assert sess.layer_index() is None
        assert sess.metrics_snapshot().get("engine.recompiles", 0) \
            == before
        assert not sess.engine._executables
    finally:
        sess.close()


def test_seed_past_32_signed_bits_builds_a_state():
    """Found on the chip (PR 25): the benchmark's driver passes seeds a
    little over 2**31, and the jitted initialiser overflowed on them."""
    cfg = lm1b.tiny_config(num_partitions=NDEV, sparse_grad_mode="slices")
    batch = lm1b.make_batch(np.random.default_rng(0), 2 * NDEV, 6,
                            cfg.vocab_size)
    losses = []
    for seed in (2**31 + 7, 7, 8):
        sess, *_ = parallax.parallel_run(
            lm1b.build_model(cfg), seed=seed,
            parallax_config=parallax.Config(
                run_option="HYBRID", search_partitions=False,
                sparse_grad_mode="slices"))
        losses.append(float(sess.run("loss", feed_dict=batch)))
        sess.close()
    assert np.isfinite(losses).all()
    assert losses[0] == losses[1] != losses[2]    # folded, not dropped


def test_lax_scan_branch_carries_the_lstm_scope():
    """``lstm_impl="xla"`` takes models/lm1b's ``lax.scan``: the same
    layer name as the kernels' path."""
    _, sess, batch = _session(lstm_impl="xla")
    try:
        sess.warmup(feed_dict=batch)
        index = sess.layer_index()
        assert "lstm" in index["scopes_found"]
        whiles = [n for n, m in index["hlo_index"].items()
                  if m["opcode"] == "while"
                  and index["layers"][n] == "lstm"]
        assert whiles
    finally:
        sess.close()


@pytest.mark.parametrize("op_name,layer,split", [
    ("jit(train_step)/jvp(lstm)/dot_general", "lstm", "dense"),
    ("jit(train_step)/transpose(jvp(lstm))/lstm_bwd/pallas_call",
     "lstm", "dense"),
    ("jit(train_step)/transpose(jvp(sampled_softmax))/dot_general",
     "sampled_softmax", "sparse"),
    # innermost wins: the candidate rows go through embedding_lookup
    ("jit(train_step)/jvp(sampled_softmax)/embedding/shard_map/all_gather",
     "embedding", "sparse"),
    ("jit(train_step)/transpose(jvp(sampled_softmax))/jvp(embedding)/"
     "shard_map/jit(_take)/scatter-add", "embedding", "sparse"),
    ("jit(train_step)/table_update/jit(_unique_sorted_mask)/sort",
     "table_update", "sparse"),
    ("jit(train_step)/dense_update/mul", "dense_update", "dense"),
    ("jit(train_step)/layer_scan/while/body/checkpoint/attention/cca_mix/"
     "dot_general", "cca_mix", "dense"),
    ("jit(train_step)/transpose(jvp(layer_scan))/while/body/checkpoint/moe/"
     "router/erf", "router", "dense"),
    ("jit(train_step)/jvp(moe)/jvp(router)/sub", "router", "dense"),
    # the compiled Mellum2 step's own: a window layer's kernels, and the
    # full layer's in the other branch of the same `cond`
    ("jit(train_step)/jvp(layer_scan)/while/body/closed_call/attention/"
     "cond/branch_1_fun/window_attention/flash_fwd_win", "window_attention",
     "dense"),
    ("jit(train_step)/transpose(jvp(layer_scan))/while/body/closed_call/"
     "checkpoint/attention/cond/branch_0_fun/flash_bwd", "attention",
     "dense"),
    # the Olmo-Hybrid step's own: the rule inside a linear layer's mixer,
    # the MLP beside it
    ("jit(train_step)/jvp(layer_scan)/while/body/while/body/checkpoint/"
     "linear_attention/delta_rule/delta_fwd", "delta_rule", "dense"),
    ("jit(train_step)/transpose(jvp(layer_scan))/while/body/while/body/"
     "checkpoint/linear_attention/dot_general", "linear_attention", "dense"),
    ("jit(train_step)/jvp(layer_scan)/while/body/checkpoint/mlp/"
     "dot_general", "mlp", "dense"),
    # a primitive or a user's scope that merely contains a layer's name
    ("jit(train_step)/jvp(my_lstm_block)/dot_general", None, None),
    ("jit(train_step)/model/embedding_norm/mul", None, None),
    ("jit(train_step)/jvp()/reduce_sum", None, None),
    ("jit(train_step)/jvp()/shard_map/psum", None, None),
    ("", None, None),
])
def test_layer_of_and_sparse_split_read_one_rule(op_name, layer, split):
    meta = {"opcode": "fusion", "op_name": op_name,
            # a file name decides nothing any more
            "source_file": "/x/parallax_tpu/ops/embedding.py"}
    assert xprof.layer_of(meta) == layer
    assert xprof.sparse_split(meta) == split


def test_no_metadata_is_no_layer():
    assert xprof.layer_of(None) is None
    assert xprof.layer_of({"opcode": "copy"}) is None
    assert xprof.sparse_split({"opcode": "copy"}) is None
    assert set(xprof.SPARSE_LAYERS) < set(xprof.LAYER_SCOPES)


def test_tuple_typed_kernel_call_indexes():
    """A Pallas kernel's custom call returns a tuple, whose type has
    spaces in it, and carries ``kernel_metadata={}`` before its
    ``metadata``: both used to lose the instruction."""
    line = ('  %lstm_bwd.1 = (bf16[20,128,8192]{2,1,0:T(8,128)(2,1)S(1)}, '
            'f32[20,128,512]{2,1,0:T(8,128)S(1)}) custom-call(%a, %b), '
            'custom_call_target="tpu_custom_call", '
            'frontend_attributes={kernel_metadata={}}, '
            'metadata={op_name="jit(f)/transpose(jvp(lstm))/lstm_bwd/'
            'pallas_call" stack_frame_id=4}')
    idx = xprof.build_hlo_index(line)
    assert idx["lstm_bwd.1"]["opcode"] == "custom-call"
    assert xprof.layer_of(idx["lstm_bwd.1"]) == "lstm"


# -- the compile cache's key holds the names ------------------------------

_SCOPED_PROGRAM = textwrap.dedent("""
    import json, os, sys
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import jax, jax.numpy as jnp
    jax.config.update("jax_platforms", "cpu")
    from parallax_tpu.compile.cache import ensure_persistent_cache
    cache_dir = ensure_persistent_cache()

    def f(x):
        with jax.named_scope(sys.argv[1]):
            return jnp.sin(x) @ x

    text = jax.jit(f).lower(
        jax.ShapeDtypeStruct((8, 8), jnp.float32)).compile().as_text()
    print(json.dumps({
        "scopes": [s for s in ("layerA", "layerB") if s in text],
        "entries": len(os.listdir(cache_dir))}))
    """)


def _checkout(tmp_path, name):
    """A directory that looks like a checkout of this commit: the
    package beside a program."""
    root = tmp_path / name
    root.mkdir()
    os.symlink(os.path.dirname(os.path.abspath(parallax.__file__)),
               root / "parallax_tpu")
    (root / "program.py").write_text(_SCOPED_PROGRAM)
    return root


def _run_scoped(root, scope, cache_dir):
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(cache_dir))
    # cache everything, as a session outside this suite does
    env.pop("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", None)
    proc = subprocess.run(
        [sys.executable, str(root / "program.py"), scope], env=env,
        cwd=str(root), capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_cached_executable_carries_this_sources_names(tmp_path):
    """Two processes, one cache directory, one computation under two
    scope names: the second reads ITS name off the executable (jax's
    default key strips the names and hands back the first's), a rerun
    adds no entry, and a second checkout directory of the same source
    shares the entries."""
    cache_dir = tmp_path / "cache"
    cache_dir.mkdir()
    here = _checkout(tmp_path, "here")
    first = _run_scoped(here, "layerA", cache_dir)
    assert first["scopes"] == ["layerA"] and first["entries"] > 0
    second = _run_scoped(here, "layerB", cache_dir)
    assert second["scopes"] == ["layerB"]
    assert second["entries"] > first["entries"]
    again = _run_scoped(here, "layerB", cache_dir)
    assert again == second
    elsewhere = _run_scoped(_checkout(tmp_path, "elsewhere"), "layerB",
                            cache_dir)
    assert elsewhere == second
