"""Set-up seen from inside (ISSUE 35): jax's own compile events in the
registry (``compile/cache.CompileEvents``), the step's lowering and its
compile as spans of their own, a count of the model's traces, and the
program's share of start-up (``startup.api_s``).

The sums are the PROCESS's, so every test reads a rise, never a value.
"""

import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import parallax_tpu as parallax
from parallax_tpu import obs
from parallax_tpu.compile.cache import compile_events
from parallax_tpu.models import lm1b
from parallax_tpu.obs import trace

PHASES = ("compile.trace_s", "compile.lower_s", "compile.backend_s")


def _dense_session(**cfg_kw):
    def init_fn(rng):
        return {"w": jax.random.normal(rng, (8, 8)) * 0.1}

    def loss_fn(params, batch):
        return jnp.mean((batch["x"] @ params["w"] - batch["y"]) ** 2)

    sess, *_ = parallax.parallel_run(
        parallax.Model(init_fn, loss_fn, optimizer=optax.sgd(0.05)),
        parallax_config=parallax.Config(
            run_option="AR", search_partitions=False,
            shape_buckets=[16], **cfg_kw))
    rng = np.random.default_rng(0)
    feed = {"x": rng.standard_normal((16, 8)).astype(np.float32),
            "y": rng.standard_normal((16, 8)).astype(np.float32)}
    return sess, feed


def _slices_session():
    cfg = lm1b.tiny_config(num_partitions=jax.device_count(),
                           sparse_grad_mode="slices")
    sess, *_ = parallax.parallel_run(
        lm1b.build_model(cfg),
        parallax_config=parallax.Config(
            run_option="HYBRID", search_partitions=False,
            sparse_grad_mode="slices", shape_buckets="auto"))
    feed = lm1b.make_batch(np.random.default_rng(0),
                           2 * jax.device_count(), 6, cfg.vocab_size)
    return sess, feed


def _rise(before):
    after = compile_events.snapshot()
    return {name: after[name] - before[name] for name in before}


@pytest.fixture
def ring():
    """A span ring of this test's own."""
    mine = trace.TraceCollector()
    prev = trace.set_collector(mine)
    yield mine
    trace.set_collector(prev)


def test_listens_once_however_many_sessions_are_built():
    from jax._src import monitoring

    a, _ = _dense_session()
    b, _ = _dense_session()
    try:
        for listeners, mine in (
                (monitoring.get_event_duration_listeners(),
                 compile_events._on_duration),
                (monitoring.get_event_listeners(),
                 compile_events._on_event),
                (monitoring.get_scalar_listeners(),
                 compile_events._on_start)):
            assert sum(1 for fn in listeners if fn == mine) == 1
        # every session's registry shows the one process's sums
        sums = compile_events.snapshot()
        assert sorted(sums) == sorted(compile_events.NAMES)
        assert len(sums) == 8
        for sess in (a, b):
            snap = sess.metrics_snapshot()
            assert {k: snap[k] for k in sums} == sums
    finally:
        a.close()
        b.close()


def test_a_fresh_jit_raises_the_three_phases_and_leaves_a_span(ring):
    def a_function_nobody_compiled_yet(x):
        return jnp.cos(x) * 3.0 + 1.0

    before = compile_events.snapshot()
    jax.jit(a_function_nobody_compiled_yet)(jnp.ones(7)) \
        .block_until_ready()
    rise = _rise(before)
    for name in PHASES:
        assert rise[name] > 0, (name, rise)
    compiles = [ev for ev in ring.events()
                if ev.name == "jax.backend_compile"]
    assert any(ev.args["fun"] == "jit(a_function_nobody_compiled_yet)"
               for ev in compiles), [ev.args for ev in compiles]


def test_a_second_is_counted_once_under_the_innermost_phase(ring):
    """An inner jit is traced inside its caller's trace, and jax
    reports both durations: summed as they come, the sleep below would
    count twice and the three sums would exceed the wall clock."""
    @jax.jit
    def inner(x):
        time.sleep(0.2)             # runs while tracing, like Python does
        return x * 2.0

    def outer(x):
        return inner(x) + jnp.arange(3.0)

    before = compile_events.snapshot()
    t0 = time.perf_counter()
    jax.jit(outer).lower(jnp.ones(3))
    wall = time.perf_counter() - t0
    rise = _rise(before)
    assert 0.19 <= rise["compile.trace_s"] <= wall
    assert sum(rise[name] for name in PHASES) <= wall
    # both traces left their span, the inner inside the outer
    spans = {ev.args["fun"]: ev for ev in ring.events()
             if ev.name == "jax.trace"}
    assert spans["outer"].ts <= spans["inner"].ts
    assert spans["inner"].dur <= spans["outer"].dur


def test_the_persistent_cache_hit_is_heard():
    from jax.experimental.compilation_cache import compilation_cache

    def cached_once_then_read_back(x):
        return jnp.tanh(x) * 5.0 - 2.0

    def compile_it():
        return jax.jit(cached_once_then_read_back).lower(
            jax.ShapeDtypeStruct((9,), jnp.float32)).compile()

    was = {name: getattr(jax.config, name) for name in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    with tempfile.TemporaryDirectory() as tmp:
        try:
            jax.config.update("jax_compilation_cache_dir", tmp)
            jax.config.update(
                "jax_persistent_cache_min_compile_time_secs", 0.0)
            jax.config.update(
                "jax_persistent_cache_min_entry_size_bytes", 0)
            compilation_cache.reset_cache()
            rises = []
            for _ in range(2):      # one call site: the key holds the
                before = compile_events.snapshot()  # callers' lines too
                compile_it()
                rises.append(_rise(before))
                jax.clear_caches()
            cold, warm = rises
            assert cold["compile.cache_misses"] == 1
            assert cold["compile.cache_hits"] == 0
            assert cold["compile.cache_retrieval_s"] == 0
            assert warm["compile.cache_hits"] == 1
            assert warm["compile.cache_requests"] == 1
            assert warm["compile.cache_misses"] == 0
            assert 0 < warm["compile.cache_retrieval_s"] \
                <= warm["compile.backend_s"]
        finally:
            for name, value in was.items():
                jax.config.update(name, value)
            compilation_cache.reset_cache()


def test_the_steps_lowering_and_compile_add_up_to_the_warmup_span(ring):
    sess, feed = _dense_session()
    try:
        sess.warmup(feed_dict=feed)
    finally:
        sess.close()
    by_name = {ev.name: ev for ev in ring.events()}
    whole, lower, comp = (by_name[n] for n in (
        "engine.warmup_compile", "engine.lower", "engine.compile"))
    for part in (lower, comp):
        assert whole.ts <= part.ts
        assert part.ts + part.dur <= whole.ts + whole.dur + 1e-6
        assert part.args["batch"] == 16
    assert lower.ts + lower.dur <= comp.ts + 1e-6
    assert abs(whole.dur - lower.dur - comp.dur) < 1e-3
    assert comp.args["cache_hit"] in (True, False)
    # jax's own span of the step's compile lies under it, by name
    assert any(ev.name == "jax.backend_compile"
               and ev.args["fun"] == "jit(train_step)"
               and comp.ts <= ev.ts for ev in ring.events())


# The numbers are the contract: a PR that takes a trace of the model
# out of start-up lowers them here. Dense: the classifier's
# ``make_jaxpr`` and the step's own trace. Slices: those two, and
# ``discover_slice_events``' ``eval_shape`` once when the engine is
# built and once more inside the step's trace.
@pytest.mark.parametrize("make, traces, spans", [
    (_dense_session, 2, {"engine.classify": 1}),
    (_slices_session, 4, {"engine.classify": 1,
                          "engine.discover_slices": 2}),
], ids=["dense", "slices"])
def test_the_models_traces_are_counted(ring, make, traces, spans):
    sess, feed = make()
    try:
        assert sess.metrics.counter("engine.model_traces").value == 0
        sess.prepare(feed)
        sess.warmup()
        float(sess.run("loss", feed_dict=feed))     # runs, traces nothing
        assert sess.metrics_snapshot()["engine.model_traces"] == traces
        assert sess.compile_stats()["model_traces"] == traces
    finally:
        sess.close()
    events = ring.events()
    for name, n in spans.items():
        assert sum(1 for ev in events if ev.name == name) == n, name
    build = next(ev for ev in events if ev.name == "engine.build")
    classify = next(ev for ev in events if ev.name == "engine.classify")
    assert build.ts <= classify.ts
    assert classify.ts + classify.dur <= build.ts + build.dur


def test_startup_api_s_counts_the_outermost_entry_once(ring):
    t0 = time.perf_counter()
    sess, feed = _dense_session()
    t1 = time.perf_counter()
    try:
        api = sess.metrics.counter("startup.api_s")
        run_span = next(ev for ev in ring.events()
                        if ev.name == "parallax.parallel_run")
        assert 0 < run_span.dur <= api.value <= t1 - t0
        had = api.value
        t2 = time.perf_counter()
        sess.warmup(feed_dict=feed)         # calls prepare() itself
        wall = time.perf_counter() - t2
        by_name = {ev.name: ev for ev in ring.events()}
        inside = by_name["session.prepare"].dur \
            + by_name["session.warmup"].dur
        assert inside <= api.value - had <= wall
        had = api.value
        t3 = time.perf_counter()
        float(sess.run("loss", feed_dict=feed))
        assert 0 < api.value - had <= time.perf_counter() - t3
        # the loop is not an entry point: nothing is added per step
        had = api.value
        for _ in sess.run_iter([feed, feed], fetches="loss"):
            pass
        assert api.value == had
        assert sess.metrics_snapshot()["startup.api_s"] == had
    finally:
        sess.close()


def test_disabled_nothing_is_counted_and_no_span_is_left(ring):
    def compiled_while_nobody_listens(x):
        return jnp.sin(x) - 4.0

    before = compile_events.snapshot()
    obs.disable()
    try:
        jax.jit(compiled_while_nobody_listens)(jnp.ones(5)) \
            .block_until_ready()
        sess, feed = _dense_session()
        try:
            sess.warmup(feed_dict=feed)
            float(sess.run("loss", feed_dict=feed))
            snap = sess.metrics_snapshot()
            stats = sess.compile_stats()
        finally:
            sess.close()
    finally:
        obs.enable()
    assert all(v == 0 for v in _rise(before).values())
    assert {k: snap[k] for k in before} == before
    assert snap["startup.api_s"] == 0
    assert snap["engine.model_traces"] == 0
    assert stats["model_traces"] == 0 and stats["jax"] == {
        k[len("compile."):]: v for k, v in before.items()}
    assert ring.events() == []
    # and the listeners' bookkeeping of open phases kept in step
    jax.jit(lambda x: x * 9.0 + 2.0)(jnp.ones(4)).block_until_ready()
    assert _rise(before)["compile.backend_s"] > 0


def test_compile_stats_shows_what_jax_reported():
    sess, feed = _dense_session()
    try:
        sess.warmup(feed_dict=feed)
        stats = sess.compile_stats()
    finally:
        sess.close()
    assert sorted(stats["jax"]) == sorted(
        name[len("compile."):] for name in compile_events.NAMES)
    assert stats["jax"]["backend_s"] > 0
    assert stats["jax"]["cache_hits"] + stats["jax"]["cache_misses"] \
        <= stats["jax"]["cache_requests"]


# -- tools/setup_account.py: the set-up's spans and phases, by name --------


def _account_tool():
    import importlib.util
    import os

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "setup_account.py")
    spec = importlib.util.spec_from_file_location("setup_account", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod, path


def test_a_spans_self_seconds_leave_out_what_lies_inside_it():
    tool, _ = _account_tool()

    def ev(name, ts, dur, tid=1, **args):
        return trace.TraceEvent(name, ts, dur, tid, f"t{tid}", args or None)

    rows = tool.span_account([
        ev("session.warmup", 0.0, 10.0),
        ev("engine.warmup_compile", 1.0, 8.0),
        ev("engine.lower", 1.0, 3.0),
        ev("jax.trace", 1.5, 2.0, fun="train_step"),
        ev("engine.compile", 4.0, 5.0),
        ev("jax.trace", 20.0, 1.0, fun="route"),
        # another thread's span overlaps and is nobody's child
        ev("prefetch", 2.0, 5.0, tid=2)])
    assert rows["session.warmup"] == {"n": 1, "total_s": 10.0,
                                      "self_s": 2.0}
    assert rows["engine.warmup_compile"]["self_s"] == 0.0
    assert rows["engine.lower"]["self_s"] == 1.0
    assert rows["jax.trace"] == {"n": 2, "total_s": 3.0, "self_s": 3.0}
    assert rows["prefetch"]["self_s"] == 5.0
    assert tool.by_fun([ev("jax.trace", 0, 2.0, fun="f"),
                        ev("jax.lower", 2, 1.0, fun="f"),
                        ev("jax.trace", 3, 0.5, fun="f"),
                        ev("engine.lower", 0, 9.0)], top=1) \
        == [["jax.trace", "f", 2.5]]


def test_the_account_tool_rehearses_a_cell():
    """The tool repeats ``benchmark/kinds/train.py``'s set-up: it must
    still run, name the benchmark's own phases, and count what the
    cell's readers count. Counts only off the chip."""
    import json
    import os
    import subprocess
    import sys

    _, path = _account_tool()
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    proc = subprocess.run(
        [sys.executable, path, "--workload", "lm1b-ref.train-1chip",
         "--seed", "3", "--rehearse-cpu"],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["rehearsal"] is True and out["static_failures"] == []
    assert "setup_s" not in out
    assert out["phases"] == sorted([
        "imports_and_resolve", "builder.build", "generator.make",
        "session.warmup", "warm_steps", "static_checks",
        "metrics_snapshot"])
    assert out["readings"]["setup_model_traces"] == 4
    for name, n in {"parallax.parallel_run": 1, "session.prepare": 1,
                    "engine.build": 1, "engine.classify": 1,
                    "engine.discover_slices": 2, "engine.init_state": 1,
                    "session.warmup": 1, "engine.warmup_compile": 1,
                    "engine.lower": 1, "engine.compile": 1,
                    "session.dispatch": 2}.items():
        assert out["spans"][name] == n, (name, out["spans"])
    assert "jit(train_step)" in out["jax"]
