"""Compile-ahead engine (ISSUE 3): batch-shape bucketing, AOT warmup,
executable/engine caching.

Covers the bucket_batch pad-and-mask transform (unit level), the
engine-level guarantees — ragged ``run_iter`` streams with bucketing
enabled never retrace (``engine.recompiles == 0``), padded tails are
loss-equal to the masked sequential reference, full batches stay
bit-identical to the unbucketed path — plus ``Engine.warmup`` making
step 0 compile-free (jax.monitoring ground truth) and the session's
engine cache reusing the partition search's measured winner instead of
rebuilding it.
"""

import json
import os
import subprocess
import sys
import threading

import jax
import numpy as np
import pytest

import parallax_tpu as parallax
from parallax_tpu.compile import bucketing
from parallax_tpu.data import bucket_batch


def _run_driver_json(cmd, check_rc: bool = True, timeout: float = 300.0,
                     attempts: int = 2) -> dict:
    """Run a driver subprocess and parse its JSON line. A child killed
    by a signal (the intermittent XLA:CPU abort these drivers exist to
    isolate) gets one retry; a clean nonzero exit with JSON output is
    returned to the caller's assertions (check_rc=False) or fails."""
    env = dict(os.environ,
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(os.path.dirname(__file__), "..")]
                   + os.environ.get("PYTHONPATH", "").split(os.pathsep)),
               # same rig as conftest: 8 emulated CPU devices
               JAX_PLATFORMS="cpu")
    if "host_platform_device_count" not in env.get("XLA_FLAGS", ""):
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                            + " --xla_force_host_platform_device_count=8"
                            ).strip()
    last = None
    for _ in range(attempts):
        proc = subprocess.run(cmd, env=env, capture_output=True,
                              text=True, timeout=timeout)
        if proc.returncode < 0 or proc.returncode in (134, 139):
            last = f"driver died with rc={proc.returncode}: " \
                   f"{proc.stderr[-500:]}"
            continue
        start = proc.stdout.find("{")
        if start < 0:
            raise AssertionError(
                f"driver printed no JSON (rc={proc.returncode}): "
                f"{proc.stdout[-300:]} {proc.stderr[-500:]}")
        # single JSON document from the first brace (the budget tool
        # pretty-prints over multiple lines; the search driver prints
        # one line)
        result = json.loads(proc.stdout[start:])
        if check_rc:
            assert proc.returncode == 0, (proc.returncode, result,
                                          proc.stderr[-500:])
        return result
    raise AssertionError(last)


# -- a mask-aware model: loss = sum(per_example * w) / sum(w) -------------


def _weighted_model(dim=8, lr=0.05):
    import jax.numpy as jnp
    import optax

    def init_fn(rng):
        return {"w": jax.random.normal(rng, (dim, dim)) * 0.1}

    def loss_fn(params, batch):
        pred = batch["x"] @ params["w"]
        per = jnp.sum((pred - batch["y"]) ** 2, axis=-1)
        w = batch["w"]
        return jnp.sum(per * w) / jnp.maximum(jnp.sum(w), 1e-8)

    return parallax.Model(init_fn, loss_fn, optimizer=optax.sgd(lr))


def _mk(rng, B, dim=8):
    x = rng.standard_normal((B, dim)).astype(np.float32)
    y = rng.standard_normal((B, dim)).astype(np.float32)
    return {"x": x, "y": y, "w": np.ones((B,), np.float32)}


def _session(**cfg_kw):
    sess, *_ = parallax.parallel_run(
        _weighted_model(),
        parallax_config=parallax.Config(run_option="AR",
                                        search_partitions=False,
                                        **cfg_kw))
    return sess


class _CompileCounter:
    """Ground-truth XLA compile counter via jax.monitoring (listeners
    can't be unregistered on this toolchain, so one global listener
    with an on/off switch)."""

    _installed = None

    def __init__(self):
        if _CompileCounter._installed is None:
            _CompileCounter._installed = self

            def _listen(event, duration, **kw):
                inst = _CompileCounter._installed
                if inst._active and "backend_compile" in event:
                    inst.count += 1

            jax.monitoring.register_event_duration_secs_listener(_listen)
        self.count = 0
        self._active = False
        inst = _CompileCounter._installed
        inst.count = 0

    def __enter__(self):
        inst = _CompileCounter._installed
        inst.count = 0
        inst._active = True
        return inst

    def __exit__(self, *exc):
        _CompileCounter._installed._active = False


# -- bucket_batch unit behavior -------------------------------------------


class TestBucketBatch:
    def test_full_batch_passes_through_unmodified(self, rng):
        b = _mk(rng, 16)
        out, bucket = bucket_batch(b, (16, 32), mask_feed="w")
        assert bucket == 16
        assert out is b  # not even copied: bit-identical by identity

    def test_ragged_batch_pads_to_bucket_and_zeroes_mask(self, rng):
        b = _mk(rng, 10)
        out, bucket = bucket_batch(b, (16, 32), mask_feed="w")
        assert bucket == 16
        assert out["x"].shape == (16, 8) and out["w"].shape == (16,)
        # real rows bit-identical; padding replicates the last example
        np.testing.assert_array_equal(out["x"][:10], b["x"])
        np.testing.assert_array_equal(out["x"][10:],
                                      np.repeat(b["x"][-1:], 6, axis=0))
        np.testing.assert_array_equal(out["w"][:10], b["w"])
        assert (out["w"][10:] == 0).all()
        # the input batch was not mutated
        assert b["x"].shape == (10, 8) and (b["w"] == 1).all()

    def test_missing_mask_feed_is_added_on_every_batch(self, rng):
        b = {"x": rng.standard_normal((10, 4)).astype(np.float32)}
        out, bucket = bucket_batch(b, (16,), mask_feed="mask")
        assert bucket == 16 and out["mask"].shape == (16,)
        assert (out["mask"][:10] == 1).all() and (out["mask"][10:] == 0).all()
        # full batch: mask still added (signature stability), all ones
        full = {"x": rng.standard_normal((16, 4)).astype(np.float32)}
        out2, _ = bucket_batch(full, (16,), mask_feed="mask")
        assert (out2["mask"] == 1).all()
        assert bucketing.batch_signature(out) == \
            bucketing.batch_signature(out2)

    def test_oversize_batch_passes_through(self, rng):
        b = _mk(rng, 64)
        out, bucket = bucket_batch(b, (16, 32), mask_feed="w")
        assert bucket is None and out is b
        # added-mask mode: the feed STRUCTURE stays stable even
        # off-bucket — a mask-consuming model must not KeyError
        b2 = {"x": rng.standard_normal((64, 4)).astype(np.float32)}
        out2, bucket2 = bucket_batch(b2, (16, 32), mask_feed="mask")
        assert bucket2 is None
        assert (out2["mask"] == 1).all() and out2["mask"].shape == (64,)

    def test_unzeroable_mask_feed_refuses_loudly(self, rng):
        """A mask feed whose leading dim is not the batch dim cannot
        have its padded rows zeroed — silently training the padding at
        full weight is corruption, so bucketing refuses."""
        b = {"x": rng.standard_normal((10, 4)).astype(np.float32),
             "w": np.ones((40,), np.float32)}  # flattened per-token
        with pytest.raises(ValueError, match="leading dim"):
            bucket_batch(b, (16,), mask_feed="w")
        # full batch: nothing to zero, passes through
        full = {"x": rng.standard_normal((16, 4)).astype(np.float32),
                "w": np.ones((40,), np.float32)}
        out, bucket = bucket_batch(full, (16,), mask_feed="w")
        assert bucket == 16 and out is full

    def test_resolve_buckets_validates(self):
        assert bucketing.resolve_buckets(None, 32) is None
        assert bucketing.resolve_buckets("auto", 24) == (24,)
        assert bucketing.resolve_buckets([32, 8, 8], 1) == (8, 32)
        with pytest.raises(ValueError, match="divisible"):
            bucketing.resolve_buckets([12], 1, local_divisor=8)
        with pytest.raises(ValueError, match="'auto'"):
            parallax.Config(shape_buckets="pow2")
        with pytest.raises(ValueError, match="positive"):
            parallax.Config(shape_buckets=[0, 8])


# -- engine-level guarantees ----------------------------------------------


class TestBucketedTraining:
    def test_ragged_run_iter_never_recompiles(self, rng):
        """The acceptance triple: recompiles == 0 over a ragged
        iterator, padded tails loss-equal to the masked sequential
        reference, full batches bit-identical to the unbucketed path."""
        sizes = [32, 32, 16, 10, 20, 32]
        batches = [_mk(rng, B) for B in sizes]

        # masked sequential reference: the SAME stream with every
        # ragged batch explicitly padded + mask-zeroed, through a
        # session with no bucketing at all
        ref_sess = _session(eager_fetch=True)
        try:
            want = []
            for b in batches:
                padded, _ = bucket_batch(b, (16, 32), mask_feed="w")
                want.append(ref_sess.run("loss", feed_dict=padded))
        finally:
            ref_sess.close()

        sess = _session(shape_buckets=[16, 32], eager_fetch=True)
        try:
            got = [float(r) for r in
                   sess.run_iter(iter(batches), fetches="loss")]
            assert sess.metrics.counter("engine.recompiles").value == 0
            # one compiled signature per BUCKET, not per batch size
            assert sess.engine._step_jit._cache_size() == 2
        finally:
            sess.close()
        # bit-identical across the whole stream — full batches take the
        # untouched fast path, padded tails the same pad the reference
        # saw; identical feeds + identical program => identical floats
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_auto_buckets_absorb_ragged_tail(self, rng):
        """shape_buckets='auto': the first batch declares the bucket,
        the documented retrace-on-tail case disappears."""
        sess = _session(shape_buckets="auto", eager_fetch=True)
        try:
            batches = [_mk(rng, 32), _mk(rng, 32), _mk(rng, 8)]
            losses = [float(r) for r in
                      sess.run_iter(iter(batches), fetches="loss")]
            assert all(np.isfinite(losses))
            assert sess.engine._buckets == (32,)
            assert sess.metrics.counter("engine.recompiles").value == 0
            assert sess.engine._step_jit._cache_size() == 1
        finally:
            sess.close()

    def test_padded_tail_loss_matches_unpadded_math(self, rng):
        """Beyond program-identity: the padded-and-masked loss equals
        the plain weighted loss over only the real examples (numpy
        reference), so the tail step trains on exactly the right
        gradient signal."""
        b = _mk(rng, 10)
        sess = _session(shape_buckets=[16], eager_fetch=True)
        try:
            got = float(sess.run("loss", feed_dict=b))
        finally:
            sess.close()
        # independent reference: same init params via an unbucketed
        # session's engine, loss computed in numpy over the 10 rows
        sess2 = _session(eager_fetch=True)
        try:
            sess2.prepare(_mk(rng, 16))
            w = np.asarray(sess2.state.params["w"])
        finally:
            sess2.close()
        per = ((b["x"] @ w - b["y"]) ** 2).sum(-1)
        want = float(per.sum() / 10.0)
        np.testing.assert_allclose(got, want, rtol=1e-5)


# -- AOT warmup ------------------------------------------------------------


class TestWarmup:
    def test_warmup_makes_step_zero_compile_free(self, rng):
        sess = _session(shape_buckets=[16, 32])
        try:
            stats = sess.warmup(feed_dict=_mk(rng, 32))
            assert sorted(stats) == [16, 32]
            assert all(t > 0 for t in stats.values())
            # compile-seconds histogram saw both compiles
            snap = sess.metrics.snapshot()
            assert snap["engine.compile_seconds"]["count"] == 2
            with _CompileCounter() as cc:
                for B in (32, 10, 16):
                    float(sess.run("loss", feed_dict=_mk(rng, B)))
            assert cc.count == 0, (
                f"{cc.count} XLA compile(s) fired after warmup")
            # every step dispatched an AOT executable; the jit cache
            # was never populated (no step ever took the compile path)
            assert sess.engine._step_jit._cache_size() == 0
            stats2 = sess.compile_stats()
            assert stats2["executable_cache"]["hits"] == 3
            assert stats2["executable_cache"]["misses"] == 0
            assert stats2["shape_buckets"] == [16, 32]
            assert sess.metrics.counter("engine.recompiles").value == 0
        finally:
            sess.close()

    def test_warmup_is_idempotent(self, rng):
        sess = _session(shape_buckets=[16])
        try:
            first = sess.warmup(feed_dict=_mk(rng, 16))
            assert sorted(first) == [16]
            again = sess.warmup()
            assert again == {}  # already compiled: skipped
        finally:
            sess.close()

    def test_background_warmup_overlaps_and_lands(self, rng):
        sess = _session(shape_buckets=[16, 32])
        try:
            sess.prepare(_mk(rng, 32))
            t = sess.warmup(background=True)
            assert isinstance(t, threading.Thread)
            t.join(timeout=120)
            assert not t.is_alive()
            assert sorted(sess.engine.warmup_seconds) == [16, 32]
            with _CompileCounter() as cc:
                float(sess.run("loss", feed_dict=_mk(rng, 10)))
            assert cc.count == 0
        finally:
            sess.close()

    def test_warmup_without_engine_or_buckets_raises(self, rng):
        sess = _session(shape_buckets=[16])
        try:
            with pytest.raises(ValueError, match="prepare"):
                sess.warmup()
        finally:
            sess.close()
        sess2 = _session()
        try:
            with pytest.raises(ValueError, match="shape_buckets"):
                sess2.warmup(feed_dict=_mk(rng, 16))
        finally:
            sess2.close()

    def test_bucketed_equals_warmed_bitwise(self, rng):
        """The AOT executable and the jit path run the same program:
        identical losses, bit for bit."""
        batches = [_mk(rng, 16), _mk(rng, 10), _mk(rng, 16)]
        cold = _session(shape_buckets=[16], eager_fetch=True)
        try:
            want = [cold.run("loss", feed_dict=b) for b in batches]
        finally:
            cold.close()
        warm = _session(shape_buckets=[16], eager_fetch=True)
        try:
            warm.warmup(feed_dict=batches[0])
            got = [warm.run("loss", feed_dict=b) for b in batches]
        finally:
            warm.close()
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# -- engine cache: the partition search reuses its measured winner --------


@pytest.fixture
def no_persistent_cache():
    """Partition-replan tests compile the same train_step over several
    meshes; on this jax build, EXECUTING a donated-arg executable
    DESERIALIZED from the persistent compilation cache (written by an
    earlier session or a previous suite run) can segfault XLA:CPU.
    The disk cache is not these tests' subject — the in-process engine
    cache is — so they compile fresh."""
    was = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", None)
    yield
    jax.config.update("jax_compilation_cache_dir", was)


class TestEngineCache:
    def _emb_model(self, V=32, D=8):
        import jax.numpy as jnp
        import optax

        from parallax_tpu.ops import embedding as emb_ops

        def init_fn(rng_):
            return {"emb": jax.random.normal(rng_, (V, D)) * 0.1}

        def loss_fn(params, batch):
            rows = emb_ops.embedding_lookup(params["emb"], batch["ids"])
            return jnp.mean(rows ** 2)

        return parallax.Model(init_fn, loss_fn,
                              optimizer=optax.sgd(0.1))

    def test_replan_back_reuses_cached_engine(self, rng,
                                              no_persistent_cache):
        """No second build of the same (p, signature): switching back
        to an already-measured candidate is a cache hit, engine object
        identity included, and stepping on it triggers no compile."""
        sess, *_ = parallax.parallel_run(
            self._emb_model(),
            parallax_config=parallax.Config(run_option="HYBRID",
                                            search_partitions=False,
                                            eager_fetch=True),
            num_partitions=2)
        try:
            feed = {"ids": rng.integers(0, 32, (16,)).astype(np.int32)}
            float(sess.run("loss", feed_dict=feed))
            e2 = sess.engine
            builds = sess.metrics.counter("engine.builds").value
            example = sess._last_example_batch
            # candidate switch (what the search does per report)
            sess._build_engine(example, 4)
            assert sess.engine is not e2
            float(sess.run("loss", feed_dict=feed))
            # ... and back to the measured winner: reused, not rebuilt
            sess._build_engine(example, 2)
            assert sess.engine is e2
            assert sess.metrics.counter("engine.builds").value == \
                builds + 1  # only the p=4 candidate was ever built anew
            assert sess.compile_stats()["engine_cache"]["hits"] == 1
            with _CompileCounter() as cc:
                loss = float(sess.run("loss", feed_dict=feed))
            assert np.isfinite(loss)
            assert cc.count == 0, (
                "stepping on the reused winner recompiled")
        finally:
            sess.close()

    def test_cache_key_survives_ragged_example(self, rng,
                                               no_persistent_cache):
        """A ragged tail as the last-seen example batch must not defeat
        the winner lookup: with buckets declared, the cache key is the
        BUCKETED signature, so ragged and full examples of one bucket
        key identically."""
        sess = _session(shape_buckets=[16], eager_fetch=True)
        try:
            float(sess.run("loss", feed_dict=_mk(rng, 16)))
            e0 = sess.engine
            builds = sess.metrics.counter("engine.builds").value
            # replan with a RAGGED example (what a tail batch leaves in
            # _last_example_batch) at the same partition count
            sess._build_engine(_mk(rng, 10), None)
            assert sess.engine is e0, "ragged example missed the cache"
            assert sess.metrics.counter("engine.builds").value == builds
        finally:
            sess.close()

    def test_live_search_builds_each_candidate_once(self):
        """End-to-end: the auto-search loop builds one engine per
        distinct candidate and settles on a cached one. Runs in a
        subprocess driver (pattern of the multihost tests): a
        multi-mesh search stacked on this suite's accumulated
        in-process state intermittently hard-crashes the XLA:CPU
        toolchain, and an isolated child turns that toolchain abort
        into a retryable failure instead of killing the whole run."""
        result = _run_driver_json(
            [sys.executable,
             os.path.join(os.path.dirname(__file__),
                          "compile_search_driver.py")])
        assert result["converged"], result
        # one build per distinct candidate — the winner was NOT rebuilt
        assert result["builds"] == len(result["tried"]), result
        assert result["winner_is_measured_candidate"], result
        # cache pruned down to the winner
        assert result["cache_len"] == 1, result


# -- compile budget (acceptance) ------------------------------------------


def test_compile_budget_guard():
    """tools/check_compile_budget.py: a two-bucket warmed run compiles
    each signature exactly once (both during warmup, none during the
    loop) and the AOT dispatch path costs <=2% of step wall-time
    (decomposed measurement — see the tool's docstring). Runs the tool
    as a subprocess (its own __main__ contract) for the same
    toolchain-crash isolation as the search driver; the tool itself
    retries a pathological microbench spike via two parent attempts.
    """
    tool = os.path.join(os.path.dirname(__file__), "..", "tools",
                        "check_compile_budget.py")
    last = None
    for _attempt in range(2):
        result = _run_driver_json(
            [sys.executable, tool, "--steps", "32"], check_rc=False)
        # compile-count invariants hold on every attempt; only the
        # overhead microbench gets the retry
        hard = [v for v in result.get("violations", [])
                if "overhead" not in v]
        assert not hard, result
        last = result
        if result["ok"]:
            break
    assert last["ok"], last


# -- persistent compilation cache wiring ----------------------------------


@pytest.fixture
def cache_decision(monkeypatch):
    """A fresh ``ensure_persistent_cache`` decision with every
    ``jax.config.update`` it makes RECORDED instead of applied (the
    suite's real cache setting is conftest's and must survive)."""
    import jax
    from parallax_tpu.compile import cache as cache_lib

    updates = {}
    monkeypatch.setattr(cache_lib, "_decided_dir", None)
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.__setitem__(k, v))
    return cache_lib, updates


def test_cache_placed_from_outside_sets_no_directory_in_code(
        cache_decision, monkeypatch, tmp_path):
    cache_lib, updates = cache_decision
    outside = str(tmp_path / "kept-by-the-machine")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", outside)
    # not a default, and not a user's explicit Config path either
    assert cache_lib.ensure_persistent_cache() == outside
    monkeypatch.setattr(cache_lib, "_decided_dir", None)
    assert cache_lib.ensure_persistent_cache(
        str(tmp_path / "from-config")) == outside
    assert "jax_compilation_cache_dir" not in updates


def test_cache_defaults_to_checkout_dir_never_a_temp_path(
        cache_decision, monkeypatch):
    import tempfile
    cache_lib, updates = cache_decision
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = cache_lib.ensure_persistent_cache()
    checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert got == os.path.join(checkout, ".jax_cache")
    assert updates["jax_compilation_cache_dir"] == got
    # fixed: no tempdir, pid or clock in it — a path that moves never hits
    assert not got.startswith(tempfile.gettempdir())
    assert str(os.getpid()) not in got
    monkeypatch.setattr(cache_lib, "_decided_dir", None)
    assert cache_lib.ensure_persistent_cache() == got


def test_cache_explicit_dir_honoured_when_env_unset_and_decided_once(
        cache_decision, monkeypatch, tmp_path):
    cache_lib, updates = cache_decision
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    mine = str(tmp_path / "xc")
    assert cache_lib.ensure_persistent_cache(mine) == mine
    assert updates["jax_compilation_cache_dir"] == mine
    # process-global: the first call decided; a later session cannot
    # move the cache (nor switch it back on behind the pipeline guard)
    updates.clear()
    assert cache_lib.ensure_persistent_cache(
        str(tmp_path / "other")) == mine
    assert updates == {}


def test_cache_threshold_exported_in_env_is_left_alone(
        cache_decision, monkeypatch):
    cache_lib, updates = cache_decision
    monkeypatch.setenv("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.5")
    cache_lib.ensure_persistent_cache()
    assert "jax_persistent_cache_min_compile_time_secs" not in updates
    monkeypatch.setattr(cache_lib, "_decided_dir", None)
    monkeypatch.delenv("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS")
    cache_lib.ensure_persistent_cache()
    assert updates["jax_persistent_cache_min_compile_time_secs"] == 0.0


def test_session_cache_dir_yields_to_the_exported_variable(tmp_path):
    """Sessions go through the helper: under conftest's exported
    JAX_COMPILATION_CACHE_DIR a Config path changes nothing."""
    import jax

    assert os.environ.get("JAX_COMPILATION_CACHE_DIR")
    was = jax.config.jax_compilation_cache_dir
    sess = _session(compilation_cache_dir=str(tmp_path / "xc"))
    try:
        assert jax.config.jax_compilation_cache_dir == was
    finally:
        sess.close()
