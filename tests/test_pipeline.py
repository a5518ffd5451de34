"""Pipeline parallelism numerics: pipelined stages == sequential apply."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from parallax_tpu.core import mesh as mesh_lib
from parallax_tpu.ops import pipeline as pp


D = 16


def _stage_fn(params, x):
    return jnp.tanh(x @ params["w"] + params["b"])


def _stacked_params(rng, n_stages):
    return {
        "w": jnp.asarray(
            rng.standard_normal((n_stages, D, D)).astype(np.float32))
        * 0.5,
        "b": jnp.asarray(
            rng.standard_normal((n_stages, D)).astype(np.float32)) * 0.1,
    }


def _sequential(params, x, n_stages):
    for s in range(n_stages):
        x = _stage_fn(jax.tree.map(lambda p: p[s], params), x)
    return x


@pytest.mark.parametrize("n_stages,M", [(2, 4), (4, 4), (4, 8), (8, 4)])
def test_matches_sequential(rng, n_stages, M):
    mesh = mesh_lib.build_mesh(num_partitions=n_stages)
    params = _stacked_params(rng, n_stages)
    r = mesh.shape["repl"]
    B = r * M * 2
    x = jnp.asarray(rng.standard_normal((B, D)).astype(np.float32))
    expected = _sequential(params, x, n_stages)
    got = jax.jit(lambda p, x: pp.pipeline_apply(
        _stage_fn, p, x, mesh, M))(params, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               rtol=2e-5, atol=2e-6)


def test_gradients_match_sequential(rng):
    n_stages, M = 4, 4
    mesh = mesh_lib.build_mesh(num_partitions=n_stages)
    params = _stacked_params(rng, n_stages)
    B = mesh.shape["repl"] * M
    x = jnp.asarray(rng.standard_normal((B, D)).astype(np.float32))

    def pipe_loss(params, x):
        return jnp.sum(pp.pipeline_apply(_stage_fn, params, x, mesh, M)
                       ** 2)

    def seq_loss(params, x):
        return jnp.sum(_sequential(params, x, n_stages) ** 2)

    gp = jax.jit(jax.grad(pipe_loss))(params, x)
    gs = jax.grad(seq_loss)(params, x)
    for name in ("w", "b"):
        np.testing.assert_allclose(np.asarray(gp[name]),
                                   np.asarray(gs[name]), rtol=5e-5,
                                   atol=5e-6, err_msg=name)


@pytest.mark.parametrize("n_stages,M", [(1, 4), (2, 4), (4, 4), (4, 8)])
def test_1f1b_matches_sequential(rng, n_stages, M):
    """1F1B fused loss+grads (stage, head, AND input cotangent) ==
    sequential forward + autodiff."""
    mesh = mesh_lib.build_mesh(num_partitions=n_stages)
    params = _stacked_params(rng, n_stages)
    head = {"wout": jnp.asarray(
        rng.standard_normal((D, D)).astype(np.float32)) * 0.3}
    B = mesh.shape["repl"] * M * 2
    x = jnp.asarray(rng.standard_normal((B, D)).astype(np.float32))
    y = jnp.asarray(rng.standard_normal((B, D)).astype(np.float32))

    def mb_loss(head, out, y_mb):
        return jnp.mean((out @ head["wout"] - y_mb) ** 2)

    loss, (g_stage, g_head, g_x) = jax.jit(
        lambda p, h, x, y: pp.pipeline_value_and_grad(
            _stage_fn, mb_loss, p, x, y, mesh, M, head_params=h)
    )(params, head, x, y)

    def seq_loss(params, head, x):
        out = _sequential(params, x, n_stages)
        return jnp.mean((out @ head["wout"] - y) ** 2)

    eloss, (ep, eh, ex) = jax.value_and_grad(seq_loss, argnums=(0, 1, 2))(
        params, head, x)
    np.testing.assert_allclose(float(loss), float(eloss), rtol=2e-5)
    for name in ("w", "b"):
        np.testing.assert_allclose(np.asarray(g_stage[name]),
                                   np.asarray(ep[name]), rtol=5e-4,
                                   atol=5e-6, err_msg=name)
    np.testing.assert_allclose(np.asarray(g_head["wout"]),
                               np.asarray(eh["wout"]), rtol=5e-4,
                               atol=5e-6)
    np.testing.assert_allclose(np.asarray(g_x), np.asarray(ex),
                               rtol=5e-4, atol=5e-6)


def _sequential_dm(params, x, S, V):
    """Sequential reference over device-major-stacked [S*V, ...] params:
    global stage g lives at row (g % S)*V + g//S."""
    for g in range(S * V):
        q = (g % S) * V + g // S
        x = _stage_fn(jax.tree.map(lambda p: p[q], params), x)
    return x


@pytest.mark.parametrize("S,V,M", [(2, 2, 4), (4, 2, 4), (2, 3, 4),
                                   (4, 2, 6)])  # 6 % 4: ragged round
def test_interleaved_matches_sequential(rng, S, V, M):
    mesh = mesh_lib.build_mesh(num_partitions=S)
    params = _stacked_params(rng, S * V)
    B = mesh.shape["repl"] * M * 2
    x = jnp.asarray(rng.standard_normal((B, D)).astype(np.float32))
    expected = _sequential_dm(params, x, S, V)
    got = jax.jit(lambda p, x: pp.pipeline_apply(
        _stage_fn, p, x, mesh, M, virtual_stages=V))(params, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               rtol=2e-5, atol=2e-6)


def test_interleaved_gradients_match_sequential(rng):
    S, V, M = 4, 2, 4
    mesh = mesh_lib.build_mesh(num_partitions=S)
    params = _stacked_params(rng, S * V)
    B = mesh.shape["repl"] * M
    x = jnp.asarray(rng.standard_normal((B, D)).astype(np.float32))

    def pipe_loss(params, x):
        return jnp.sum(pp.pipeline_apply(_stage_fn, params, x, mesh, M,
                                         virtual_stages=V) ** 2)

    def seq_loss(params, x):
        return jnp.sum(_sequential_dm(params, x, S, V) ** 2)

    gp = jax.jit(jax.grad(pipe_loss))(params, x)
    gs = jax.grad(seq_loss)(params, x)
    for name in ("w", "b"):
        # S*V=8-stage tanh chain: float32 summation-order noise is
        # ~3e-5 abs on O(1) gradients; tolerance covers noise only
        np.testing.assert_allclose(np.asarray(gp[name]),
                                   np.asarray(gs[name]), rtol=1e-3,
                                   atol=1e-4, err_msg=name)


@pytest.mark.parametrize("S,V,M", [(2, 2, 4), (4, 2, 4), (2, 3, 6),
                                   (4, 2, 6)])  # 6 % 4: ragged round
def test_interleaved_1f1b_matches_sequential(rng, S, V, M):
    """Interleaved 1F1B fused loss+grads == sequential autodiff."""
    mesh = mesh_lib.build_mesh(num_partitions=S)
    params = _stacked_params(rng, S * V)
    head = {"wout": jnp.asarray(
        rng.standard_normal((D, D)).astype(np.float32)) * 0.3}
    B = mesh.shape["repl"] * M
    x = jnp.asarray(rng.standard_normal((B, D)).astype(np.float32))
    y = jnp.asarray(rng.standard_normal((B, D)).astype(np.float32))

    def mb_loss(head, out, y_mb):
        return jnp.mean((out @ head["wout"] - y_mb) ** 2)

    loss, (g_stage, g_head, g_x) = jax.jit(
        lambda p, h, x, y: pp.pipeline_value_and_grad(
            _stage_fn, mb_loss, p, x, y, mesh, M, head_params=h,
            virtual_stages=V)
    )(params, head, x, y)

    def seq_loss(params, head, x):
        out = _sequential_dm(params, x, S, V)
        return jnp.mean((out @ head["wout"] - y) ** 2)

    eloss, (ep, eh, ex) = jax.value_and_grad(seq_loss, argnums=(0, 1, 2))(
        params, head, x)
    np.testing.assert_allclose(float(loss), float(eloss), rtol=2e-5)
    for name in ("w", "b"):
        np.testing.assert_allclose(np.asarray(g_stage[name]),
                                   np.asarray(ep[name]), rtol=1e-3,
                                   atol=1e-4, err_msg=name)
    np.testing.assert_allclose(np.asarray(g_head["wout"]),
                               np.asarray(eh["wout"]), rtol=1e-3,
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(g_x), np.asarray(ex),
                               rtol=1e-3, atol=1e-4)


def test_stage_order_permutation_roundtrip():
    """Device-major slot q holds global stage (q%V)*S + q//V; the
    permutation is a bijection and identity when V=1."""
    assert pp.stage_order_permutation(4, 1) == [0, 1, 2, 3]
    perm = pp.stage_order_permutation(4, 2)
    assert sorted(perm) == list(range(8))
    # device 0's rows (q=0,1) hold stages 0 and 4 — its two chunks
    assert perm[0] == 0 and perm[1] == 4


def test_1f1b_buffer_is_o_s_not_o_m():
    """The in-flight buffer bound is 2S-1 slots, independent of M."""
    assert pp.inflight_buffer_size(num_stages=4, num_microbatches=64) == 7
    assert pp.inflight_buffer_size(num_stages=2, num_microbatches=128) == 3
    # small-M clamp: never allocate more slots than microbatches
    assert pp.inflight_buffer_size(num_stages=8, num_microbatches=4) == 4


# -- the third mesh axis (ISSUE 18): stages on 'pipe', not 'shard' --------


def test_build_mesh_3_tuple_shape_and_validation():
    mesh = mesh_lib.build_mesh(shape=(2, 2, 2))
    assert mesh.axis_names == (mesh_lib.AXIS_REPL, mesh_lib.AXIS_SHARD,
                               mesh_lib.AXIS_PIPE)
    assert dict(zip(mesh.axis_names, mesh.devices.shape)) == {
        "repl": 2, "shard": 2, "pipe": 2}
    # pp=1 keeps the exact legacy 2-axis mesh — no vestigial axis
    flat = mesh_lib.build_mesh(shape=(4, 2, 1))
    assert flat.axis_names == (mesh_lib.AXIS_REPL, mesh_lib.AXIS_SHARD)
    with pytest.raises(ValueError, match="dp\\*tp\\*pp"):
        mesh_lib.build_mesh(shape=(2, 2, 3))


def test_pipeline_axis_helpers():
    three = mesh_lib.build_mesh(shape=(2, 2, 2))
    two = mesh_lib.build_mesh(shape=(4, 2))
    assert mesh_lib.pipeline_axis(three) == mesh_lib.AXIS_PIPE
    assert mesh_lib.pipeline_axis(two) == mesh_lib.AXIS_SHARD
    assert mesh_lib.pipeline_stage_count(three) == 2
    assert mesh_lib.pipeline_stage_count(two) == 2


def test_resolve_spec_folds_pipe_onto_shard():
    from jax.sharding import PartitionSpec as P
    three = mesh_lib.build_mesh(shape=(2, 2, 2))
    two = mesh_lib.build_mesh(shape=(4, 2))
    spec = P(mesh_lib.AXIS_PIPE)
    # a 3-axis mesh keeps the declared spec; a 2-axis mesh maps the
    # pipeline axis onto 'shard' so one declaration runs on both
    assert mesh_lib.resolve_spec(spec, three) == spec
    assert mesh_lib.resolve_spec(spec, two) == P(mesh_lib.AXIS_SHARD)
    keep = P(mesh_lib.AXIS_REPL, None)
    assert mesh_lib.resolve_spec(keep, two) == keep


def test_pipeline_engine_guard_disables_persistent_cache(monkeypatch,
                                                         tmp_path):
    """Reloading a persistently-cached pipeline-schedule executable
    segfaults this XLA:CPU toolchain (a hard process kill — the
    reason tier-1's pipeline session proofs run in subprocess
    drivers), so the first pipeline engine in a process must switch
    the persistent compilation cache off, once, before any lookup."""
    from parallax_tpu.core import engine as engine_lib

    monkeypatch.setattr(engine_lib, "_pipeline_cache_guarded", False)
    prev = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    try:
        engine_lib._guard_persistent_cache_for_pipeline()
        assert jax.config.jax_compilation_cache_dir is None
        # one-way per process: once tripped, a later re-enable by the
        # user is respected (the guard never fires twice)
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        engine_lib._guard_persistent_cache_for_pipeline()
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


@pytest.mark.parametrize("shape", [(2, 2, 2), (4, 1, 2), (2, 1, 4)])
def test_matches_sequential_on_3_axis_mesh(rng, shape):
    """Stages ring over 'pipe'; 'repl' carries data parallelism and
    'shard' runs identical program copies — numerics must match the
    2-axis path exactly."""
    M = 4
    mesh = mesh_lib.build_mesh(shape=shape)
    S = mesh.shape[mesh_lib.AXIS_PIPE]
    params = _stacked_params(rng, S)
    B = mesh.shape["repl"] * M * 2
    x = jnp.asarray(rng.standard_normal((B, D)).astype(np.float32))
    expected = _sequential(params, x, S)
    got = jax.jit(lambda p, x: pp.pipeline_apply(
        _stage_fn, p, x, mesh, M))(params, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("shape", [(2, 2, 2), (4, 1, 2), (2, 1, 4)])
def test_1f1b_matches_sequential_on_3_axis_mesh(rng, shape):
    M = 4
    mesh = mesh_lib.build_mesh(shape=shape)
    S = mesh.shape[mesh_lib.AXIS_PIPE]
    params = _stacked_params(rng, S)
    head = {"wout": jnp.asarray(
        rng.standard_normal((D, D)).astype(np.float32)) * 0.3}
    B = mesh.shape["repl"] * M
    x = jnp.asarray(rng.standard_normal((B, D)).astype(np.float32))
    y = jnp.asarray(rng.standard_normal((B, D)).astype(np.float32))

    def mb_loss(head, out, y_mb):
        return jnp.mean((out @ head["wout"] - y_mb) ** 2)

    loss, (g_stage, g_head, g_x) = jax.jit(
        lambda p, h, x, y: pp.pipeline_value_and_grad(
            _stage_fn, mb_loss, p, x, y, mesh, M, head_params=h)
    )(params, head, x, y)

    def seq_loss(params, head, x):
        out = _sequential(params, x, S)
        return jnp.mean((out @ head["wout"] - y) ** 2)

    eloss, (ep, eh, ex) = jax.value_and_grad(seq_loss, argnums=(0, 1, 2))(
        params, head, x)
    np.testing.assert_allclose(float(loss), float(eloss), rtol=2e-5)
    for name in ("w", "b"):
        np.testing.assert_allclose(np.asarray(g_stage[name]),
                                   np.asarray(ep[name]), rtol=5e-4,
                                   atol=5e-6, err_msg=name)
    np.testing.assert_allclose(np.asarray(g_head["wout"]),
                               np.asarray(eh["wout"]), rtol=5e-4,
                               atol=5e-6)
    np.testing.assert_allclose(np.asarray(g_x), np.asarray(ex),
                               rtol=5e-4, atol=5e-6)


def test_ragged_interleaved_warns_once(rng, caplog):
    """M % S != 0 at V > 1 runs masked bubble entries — pure waste the
    user should hear about exactly once per (M, S, V)."""
    import logging
    S, V, M = 2, 2, 3
    mesh = mesh_lib.build_mesh(num_partitions=S)
    params = _stacked_params(rng, S * V)
    B = mesh.shape["repl"] * M
    x = jnp.asarray(rng.standard_normal((B, D)).astype(np.float32))
    pp._ragged_warned.discard((M, S, V))
    with caplog.at_level(logging.WARNING, logger="PARALLAX"):
        pp.pipeline_apply(_stage_fn, params, x, mesh, M,
                          virtual_stages=V)
    ragged = [r for r in caplog.records
              if "pads to" in r.getMessage()]
    assert len(ragged) == 1, caplog.records
    # rounded-M figure matches the cost model's pricing
    assert "pads to 4 entries" in ragged[0].getMessage()
    caplog.clear()
    with caplog.at_level(logging.WARNING, logger="PARALLAX"):
        pp.pipeline_apply(_stage_fn, params, x, mesh, M,
                          virtual_stages=V)
    assert not [r for r in caplog.records
                if "pads to" in r.getMessage()]
    # aligned schedules never warn
    pp._ragged_warned.discard((4, S, V))
    x4 = jnp.asarray(rng.standard_normal(
        (mesh.shape["repl"] * 4, D)).astype(np.float32))
    with caplog.at_level(logging.WARNING, logger="PARALLAX"):
        pp.pipeline_apply(_stage_fn, params, x4, mesh, 4,
                          virtual_stages=V)
    assert not [r for r in caplog.records
                if "pads to" in r.getMessage()]


def _run_parity_driver(cmd, timeout=480.0, attempts=2):
    """Subprocess driver with crash-retry (the test_tune.py pattern):
    in-process multi-mesh session work intermittently hard-crashes
    this XLA:CPU toolchain, and a toolchain abort is a process kill a
    try/except can never catch — isolation makes a crash cost one
    retry, never the pytest process."""
    import json

    env = dict(os.environ,
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(os.path.dirname(__file__), "..")]
                   + ([os.environ["PYTHONPATH"]]
                      if os.environ.get("PYTHONPATH") else [])),
               JAX_PLATFORMS="cpu")
    last = None
    for _ in range(attempts):
        proc = subprocess.run(cmd, env=env, capture_output=True,
                              text=True, timeout=timeout)
        if proc.returncode < 0 or proc.returncode in (134, 139):
            last = (f"driver died with rc={proc.returncode}: "
                    f"{proc.stderr[-500:]}")
            continue
        start = proc.stdout.find("{")
        assert start >= 0, (
            f"driver printed no JSON (rc={proc.returncode}): "
            f"{proc.stdout[-300:]} {proc.stderr[-500:]}")
        result = json.loads(proc.stdout[start:])
        assert proc.returncode == 0, (proc.returncode, result,
                                      proc.stderr[-800:])
        return result
    raise AssertionError(last)


def test_session_pp_plan_loss_parity():
    """Acceptance (ISSUE 18): a tuner-emitted pp>1 plan trains to the
    SAME losses as the pp=1 baseline (4-decimal tolerance), for BOTH
    schedules, proven in one isolated driver process
    (tests/pp_parity_driver.py — the driver's docstring has the
    init-then-reshard numerics contract and the isolation
    rationale)."""
    result = _run_parity_driver(
        [sys.executable,
         os.path.join(os.path.dirname(__file__),
                      "pp_parity_driver.py")])
    assert set(result["pp2"]) == {"gpipe", "1f1b"}
    assert len(result["base"]) == 3
    for schedule, losses in result["pp2"].items():
        np.testing.assert_allclose(losses, result["base"], atol=1e-4,
                                   err_msg=schedule)


@pytest.mark.slow
@pytest.mark.parametrize("schedule,virtual", [("gpipe", 1), ("1f1b", 1),
                                              ("gpipe", 2), ("1f1b", 2)])
def test_pipeline_lm_through_engine(rng, schedule, virtual):
    """'pipeline' mode (both schedules, interleaved and not): stages
    sharded over 'shard', trajectory matches pure data parallelism
    (same math, pipelined schedule; 1F1B additionally fuses the
    backward via Model.value_and_grad_fn; virtual=2 interleaves two
    chunks per device with device-major layer storage)."""
    import parallax_tpu as parallax
    from parallax_tpu.models import long_context as lc

    batches = [lc.make_batch(rng, 8, 16, 512) for _ in range(3)]
    stages = 4 if virtual == 1 else 2

    def run(parallelism, num_partitions):
        cfg = lc.tiny_config(num_layers=4, max_len=16)
        cfg.parallelism = parallelism
        cfg.num_microbatches = 2
        cfg.pipeline_schedule = schedule
        if parallelism == "pipeline" and virtual > 1:
            cfg.virtual_stages = virtual
            cfg.pipeline_stages = stages
        sess, *_ = parallax.parallel_run(
            lc.build_model(cfg),
            parallax_config=parallax.Config(run_option="HYBRID",
                                            search_partitions=False),
            num_partitions=num_partitions)
        losses = [sess.run("loss", feed_dict=b) for b in batches]
        state = sess.state
        sess.close()
        return losses, state

    pipe_losses, pipe_state = run("pipeline", stages)
    data_losses, _ = run("data", 1)
    # stage params sharded: each device holds num_layers/stages rows
    w = pipe_state.params["blocks_stacked"]["wqkv"]
    assert w.sharding.shard_shape(w.shape)[0] == 4 // stages
    np.testing.assert_allclose(pipe_losses, data_losses, rtol=2e-3)
