"""Plan observatory tests (ISSUE 13): xprof parser on a committed
golden trace, HLO-metadata joins, calibration store round-trip +
nominal fallback, memwatch ring/gauges/exporter, OOM preflight
refusal, and the subprocess attribution guard."""

import json
import os
import subprocess
import sys
import urllib.request

import numpy as np
import pytest

import parallax_tpu as parallax
from parallax_tpu.common.config import TuneConfig
from parallax_tpu.obs import memwatch as memwatch_lib, xprof
from parallax_tpu.obs.export import TelemetryExporter
from parallax_tpu.obs.flightrec import FlightRecorder
from parallax_tpu.obs.memwatch import MemWatch
from parallax_tpu.obs.metrics import MetricsRegistry
from parallax_tpu.tune import calibrate, costmodel
from parallax_tpu.tune.costmodel import CostInputs, Plan
from parallax_tpu.tune.search import MeshSearch

GOLDEN = os.path.join(os.path.dirname(__file__), "data",
                      "golden_trace.json")


def _golden():
    with open(GOLDEN) as f:
        return json.load(f)


# -- taxonomy ---------------------------------------------------------------

class TestCategorize:
    @pytest.mark.parametrize("name,cat,kind", [
        ("all-reduce.1", "collective", "all-reduce"),
        ("all-reduce-start", "collective", "all-reduce"),
        ("all-gather.17", "collective", "all-gather"),
        ("reduce-scatter", "collective", "reduce-scatter"),
        ("all-to-all.3", "collective", "all-to-all"),
        ("collective-permute.2", "collective", "collective-permute"),
        ("collective-broadcast", "collective",
         "collective-broadcast"),
        ("copy.2", "copy", None),
        ("copy-done.1", "copy", None),
        ("transpose.4", "copy", None),
        ("infeed", "infeed", None),
        ("outfeed.1", "outfeed", None),
        ("dot.1", "compute", None),
        ("while", "compute", None),
        ("reduce-window", "compute", None),
    ])
    def test_taxonomy(self, name, cat, kind):
        assert xprof.categorize(name) == (cat, kind)

    def test_fusions_are_compute_whatever_their_root(self):
        # a fused copy/collective-shaped NAME is compiled arithmetic
        assert xprof.categorize("copy_subtract_fusion") == \
            ("compute", None)
        assert xprof.categorize("broadcast_multiply_fusion.1") == \
            ("compute", None)


def test_merge_intervals_overlap_and_containment():
    merged = xprof.merge_intervals(
        [(0, 10), (5, 7), (9, 15), (20, 25), (24, 30), (40, 41)])
    assert merged == [(0, 15), (20, 30), (40, 41)]


# -- golden fixture ---------------------------------------------------------

class TestGoldenTrace:
    def test_device_track_filtering(self):
        ops, basis = xprof.device_op_events(_golden())
        assert basis == "hlo_op"
        # the python-track PjitFunction and the argless
        # ThunkExecutor runtime event are filtered out
        assert len(ops) == 8
        assert {e["name"] for e in ops} == {
            "while", "dot.1", "all-reduce", "copy.2", "fusion.3",
            "infeed", "all-gather.1"}

    def test_overlap_merge_and_residual_accounting(self):
        a = xprof.attribute(_golden(), steps=2)
        # busy union: (0,100)+(110,120)+(1200,1240)+(1250,1300)
        assert a.attributed_ms == pytest.approx(0.200, abs=1e-6)
        # per-step envelopes split at the single largest gap (1080us
        # of host time): (0,120) + (1200,1300) = 220us device wall
        assert a.wall_ms == pytest.approx(0.220, abs=1e-6)
        assert a.residual_ms == pytest.approx(0.020, abs=1e-6)
        assert a.coverage == pytest.approx(200 / 220, abs=1e-3)
        assert a.window_span_ms == pytest.approx(1.300, abs=1e-6)
        assert a.inter_step_ms == pytest.approx(1.080, abs=1e-6)
        assert a.tracks == 2 and a.events == 8

    def test_self_durations_resolve_nesting(self):
        a = xprof.attribute(_golden(), steps=2)
        ops = {r["op"]: r for r in a.top_ops}
        # the while op's 100us contains dot.1 (30) + all-reduce (20):
        # self = 50, never double-counted
        assert ops["while"]["self_ms"] == pytest.approx(0.050,
                                                        abs=1e-6)
        # dot.1 aggregates across both tracks: 30 + 50
        assert ops["dot.1"]["self_ms"] == pytest.approx(0.080,
                                                        abs=1e-6)
        assert ops["dot.1"]["count"] == 2
        total_self = sum(r["self_ms"]
                         for r in a.by_category.values())
        assert total_self == pytest.approx(0.260, abs=1e-6)

    def test_category_taxonomy_totals(self):
        a = xprof.attribute(_golden(), steps=2)
        c = a.by_category
        assert c["compute"]["self_ms"] == pytest.approx(0.170,
                                                        abs=1e-6)
        assert c["collective"]["self_ms"] == pytest.approx(0.070,
                                                           abs=1e-6)
        assert c["copy"]["self_ms"] == pytest.approx(0.010, abs=1e-6)
        assert c["infeed"]["self_ms"] == pytest.approx(0.010,
                                                       abs=1e-6)
        assert sum(r["share"] for r in c.values()) == \
            pytest.approx(1.0, abs=1e-3)
        assert a.collectives["all-reduce"]["self_ms"] == \
            pytest.approx(0.020, abs=1e-6)
        assert a.collectives["all-gather"]["self_ms"] == \
            pytest.approx(0.050, abs=1e-6)

    def test_unknown_steps_keeps_conservative_span_wall(self):
        a = xprof.attribute(_golden(), steps=None)
        assert a.wall_ms == pytest.approx(1.300, abs=1e-6)
        assert a.coverage == pytest.approx(200 / 1300, abs=1e-3)
        assert a.inter_step_ms == 0.0

    def test_by_module_split(self):
        a = xprof.attribute(_golden(), steps=2)
        assert a.by_module["jit_step"] == pytest.approx(0.250,
                                                        abs=1e-6)
        assert a.by_module["jit_init"] == pytest.approx(0.010,
                                                        abs=1e-6)

    def test_empty_trace_reports_nothing_not_garbage(self):
        a = xprof.attribute({"traceEvents": []}, steps=4)
        assert a.events == 0 and a.coverage is None
        assert a.as_dict()["step_wall_ms"] is None


# -- HLO metadata joins -----------------------------------------------------

_HLO_TEXT = """\
HloModule jit_step

ENTRY %main.10 (Arg_0.1: f32[8]) -> f32[8] {
  %dot.1 = f32[8]{0} dot(f32[8]{0} %Arg_0.1, f32[8]{0} %Arg_0.1), metadata={op_name="jit(step)/jit(main)/jvp(lstm)/dot_general" source_file="/repo/parallax_tpu/models/lm1b.py" source_line=42}
  %all-gather.1 = f32[8]{0} all-gather(f32[8]{0} %dot.1), metadata={op_name="jit(step)/jit(main)/transpose(jvp(sampled_softmax))/embedding/shard_map/all_gather" source_file="/repo/parallax_tpu/ops/embedding.py" source_line=100}
  ROOT %add.2 = f32[8]{0} add(f32[8]{0} %dot.1, f32[8]{0} %all-gather.1)
}
"""


class TestHloIndex:
    def test_index_parses_names_opcodes_metadata(self):
        idx = xprof.build_hlo_index(_HLO_TEXT)
        assert idx["dot.1"]["opcode"] == "dot"
        assert idx["dot.1"]["source_file"].endswith("lm1b.py")
        assert idx["all-gather.1"]["opcode"] == "all-gather"
        # metadata-less instructions still index (opcode only)
        assert idx["add.2"]["opcode"] == "add"
        assert "op_name" not in idx["add.2"]

    def test_layer_mapping_strips_jit_wrappers(self):
        """The layer is the innermost declared scope of the op_name
        (xprof.LAYER_SCOPES), AD's wrappers taken off; a path with no
        declared scope is no layer, whatever file emitted it."""
        idx = xprof.build_hlo_index(_HLO_TEXT)
        assert xprof.layer_of(idx["dot.1"]) == "lstm"
        assert xprof.layer_of(idx["all-gather.1"]) == "embedding"
        assert xprof.layer_of(idx["add.2"]) is None
        assert xprof.layer_of(None) is None

    def test_dense_sparse_split_by_source(self):
        """The split follows the layer (xprof.SPARSE_LAYERS), not the
        source file: jax 0.9's compiled text names no file."""
        idx = xprof.build_hlo_index(_HLO_TEXT)
        assert xprof.sparse_split(idx["all-gather.1"]) == "sparse"
        assert xprof.sparse_split(idx["dot.1"]) == "dense"
        assert xprof.sparse_split(idx["add.2"]) is None
        assert xprof.sparse_split(
            {"opcode": "dot", "source_file": "x/ops/embedding.py",
             "op_name": "jit(s)/model/emb/dot"}) is None

    def test_attribution_joins_index(self):
        idx = {"dot.1": {"opcode": "dot",
                         "op_name": "jit(s)/jit(main)/lstm/dot"}}
        a = xprof.attribute(_golden(), steps=2, hlo_index=idx)
        ops = {r["op"]: r for r in a.top_ops}
        assert ops["dot.1"]["layer"] == "lstm"
        assert ops["dot.1"]["split"] == "dense"
        assert a.layers["lstm"] == pytest.approx(0.080, abs=1e-6)
        # unmapped ops stay visible, never silently dropped
        assert a.dense_sparse["dense_self_ms"] == \
            pytest.approx(0.080, abs=1e-6)
        assert a.dense_sparse["unmapped_self_ms"] == \
            pytest.approx(0.180, abs=1e-6)

    def test_direction_of_transpose_scopes(self):
        """ISSUE 14 backward-attribution join: XLA's AD-transpose
        scope marks the backward; jit wrappers and op names merely
        CONTAINING 'transpose' (the copy-category opcode) don't."""
        assert xprof.direction_of(
            {"op_name": "jit(s)/transpose(jvp(f))/mul"}) == "backward"
        assert xprof.direction_of(
            {"op_name": "jit(s)/jit(main)/lstm/dot"}) == "forward"
        # an op NAMED transpose is a forward copy, not the backward
        assert xprof.direction_of(
            {"op_name": "jit(s)/layer_a/transpose"}) == "forward"
        assert xprof.direction_of({"opcode": "dot"}) is None
        assert xprof.direction_of(None) is None

    def test_attribution_fwd_bwd_split(self):
        idx = {"dot.1": {"opcode": "dot",
                         "op_name":
                         "jit(s)/transpose(jvp(step))/layer_a/dot"}}
        a = xprof.attribute(_golden(), steps=2, hlo_index=idx)
        assert a.fwd_bwd["backward_self_ms"] == \
            pytest.approx(0.080, abs=1e-6)
        assert a.fwd_bwd["forward_self_ms"] == 0.0
        assert a.fwd_bwd["unmapped_self_ms"] == \
            pytest.approx(0.180, abs=1e-6)
        # no index: everything unmapped, never fabricated
        a0 = xprof.attribute(_golden(), steps=2)
        assert a0.fwd_bwd["forward_self_ms"] == 0.0
        assert a0.fwd_bwd["backward_self_ms"] == 0.0


# -- calibration store ------------------------------------------------------

class TestCalibration:
    def test_predicted_terms_collapse(self):
        terms = {"compute_s": 2.0, "hbm_s": 3.0, "wire_dense_s": 1.0,
                 "wire_zero_shard_s": 0.5, "wire_table_s": 0.25,
                 "wire_hidden_s": 0.25}
        p = calibrate.predicted_terms_from_cost(terms)
        assert p == {"on_chip": 3.0, "wire": 1.5}

    def test_measured_terms_from_attribution(self):
        a = xprof.attribute(_golden(), steps=2).as_dict()
        m = calibrate.measured_terms_from_attribution(a,
                                                      num_devices=2)
        # collective 0.070ms over 2 steps x 2 devices -> seconds
        assert m["wire"] == pytest.approx(0.070e-3 / 4, rel=1e-6)
        assert m["on_chip"] == pytest.approx(0.190e-3 / 4, rel=1e-6)

    def test_round_trip(self, tmp_path):
        rec = calibrate.build_record({"on_chip": 2.0, "wire": 1.0},
                                     {"on_chip": 1.0, "wire": 4.0},
                                     basis="test")
        path = str(tmp_path / "cal.json")
        calibrate.save(path, rec)
        loaded = calibrate.load(path)
        assert loaded is not None
        assert calibrate.ratios(loaded) == {"on_chip": 2.0,
                                            "wire": 0.25}

    def test_nominal_fallback_on_missing_and_corrupt(self, tmp_path):
        assert calibrate.load(str(tmp_path / "nope.json")) is None
        assert calibrate.load(None) is None
        bad = tmp_path / "bad.json"
        bad.write_text("{not json")
        assert calibrate.load(str(bad)) is None
        foreign = tmp_path / "foreign.json"
        foreign.write_text(json.dumps({"format": "something-else"}))
        assert calibrate.load(str(foreign)) is None

    def test_zero_measured_term_records_null_not_garbage(self):
        rec = calibrate.build_record({"on_chip": 2.0, "wire": 1.0},
                                     {"on_chip": 1.0, "wire": 0.0})
        assert rec["terms"]["wire"]["predicted_over_measured"] is None
        assert calibrate.ratios(rec) == {"on_chip": 2.0}

    def test_insane_ratio_is_refused(self):
        rec = calibrate.build_record({"wire": 1e9}, {"wire": 1e-9})
        assert calibrate.ratios(rec) is None

    def test_predict_applies_calibration(self):
        plan = Plan(dp=2, tp=1, run_option="AR")
        inputs = CostInputs(flops=2e12, hbm_bytes=0,
                            dense_grad_bytes=int(1e9),
                            num_devices=2, peak_flops=1e12,
                            hbm_bps=1e12, ici_bps=1e9)
        base = costmodel.predict(plan, inputs)
        cal = costmodel.predict(
            plan, __import__("dataclasses").replace(
                inputs, calibration={"on_chip": 2.0, "wire": 0.5}))
        # on_chip halves (predicted 2x too high), wire doubles
        assert cal.terms["compute_s"] == pytest.approx(
            base.terms["compute_s"] / 2)
        assert cal.terms["wire_dense_s"] == pytest.approx(
            base.terms["wire_dense_s"] * 2)
        assert cal.calibration == {"on_chip": 2.0, "wire": 0.5}
        assert base.calibration is None


# -- memwatch ---------------------------------------------------------------

def _fake_stats(in_use=50, limit=100):
    return {"tpu:0": {"bytes_in_use": in_use,
                      "peak_bytes_in_use": in_use + 5,
                      "bytes_limit": limit},
            "tpu:1": {"bytes_in_use": 10,
                      "peak_bytes_in_use": 12,
                      "bytes_limit": limit}}


class TestMemWatch:
    def test_ring_and_gauges(self):
        reg = MetricsRegistry()
        mw = MemWatch(reg, stats_fn=lambda: _fake_stats(40))
        mw.sample(0)
        mw.sample(1)
        assert mw.total_samples == 2
        snap = reg.snapshot()
        assert snap["device.tpu:0.bytes_in_use"] == 40
        assert snap["device.tpu:0.peak_bytes"] == 45
        assert snap["device.tpu:0.bytes_limit"] == 100
        assert snap["device.tpu:1.bytes_in_use"] == 10
        assert mw.live_peak_bytes() == 45
        s = mw.stats()
        assert s["samples"] == 2 and len(s["ring"]) == 2

    def test_oom_risk_flight_incident(self, tmp_path):
        reg = MetricsRegistry()
        flight = FlightRecorder(flight_dir=str(tmp_path),
                                registry=reg)
        mw = MemWatch(reg, flight=flight, oom_risk_frac=0.9,
                      stats_fn=lambda: _fake_stats(95))
        mw.sample(7)
        assert reg.counter("memwatch.oom_risk_events").value == 1
        assert len(flight.dump_paths) == 1
        doc = json.loads(open(flight.dump_paths[0]).read())
        assert doc["reason"] == "oom_risk"
        assert doc["detail"]["devices"][0]["device"] == "tpu:0"
        assert doc["detail"]["devices"][0]["frac"] == 0.95

    def test_below_risk_threshold_is_silent(self, tmp_path):
        flight = FlightRecorder(flight_dir=str(tmp_path))
        mw = MemWatch(MetricsRegistry(), flight=flight,
                      oom_risk_frac=0.9,
                      stats_fn=lambda: _fake_stats(50))
        mw.sample(0)
        assert flight.dump_paths == []

    def test_killswitch_no_ring_no_stats_call(self):
        calls = []

        def counting_stats():
            calls.append(1)
            return _fake_stats()

        mw = MemWatch(MetricsRegistry(), stats_fn=counting_stats)
        from parallax_tpu import obs
        obs.disable()
        try:
            mw.sample(0)
        finally:
            obs.enable()
        assert mw.total_samples == 0 and calls == []

    def test_statless_backend_latch(self):
        calls = []

        def empty_stats():
            calls.append(1)
            return {}

        mw = MemWatch(MetricsRegistry(), stats_fn=empty_stats)
        for i in range(10):
            mw.sample(i)
        # three empty polls prove the backend statless; no more polls
        assert len(calls) == 3
        assert mw.total_samples == 0

    def test_every_knob_downsamples(self):
        mw = MemWatch(MetricsRegistry(), every=4,
                      stats_fn=lambda: _fake_stats())
        for i in range(8):
            mw.sample(i)
        assert mw.total_samples == 2

    def test_exporter_serves_device_gauges(self):
        reg = MetricsRegistry()
        mw = MemWatch(reg, stats_fn=lambda: _fake_stats(33))
        mw.sample(0)
        with TelemetryExporter.for_registry(reg, source="s0") as exp:
            body = urllib.request.urlopen(exp.url,
                                          timeout=10).read().decode()
        assert 'parallax_device_tpu_0_bytes_in_use{source="s0"} 33' \
            in body
        assert "parallax_device_tpu_0_bytes_limit" in body
        assert "parallax_device_tpu_1_peak_bytes" in body

    def test_compiled_memory_on_real_executable(self):
        import jax
        import jax.numpy as jnp
        f = jax.jit(lambda x: (x @ x).sum())
        compiled = f.lower(
            jax.ShapeDtypeStruct((64, 64), jnp.float32)).compile()
        m = memwatch_lib.compiled_memory(compiled)
        assert m is not None and m["peak_bytes"] > 0
        assert m["argument_size_in_bytes"] == 64 * 64 * 4

    def test_hbm_budget_resolution(self):
        tc = TuneConfig(hbm_budget_gb=2.0)
        assert memwatch_lib.hbm_budget_bytes(tc) == int(2e9)
        assert memwatch_lib.hbm_budget_bytes(
            None, stats_fn=lambda: _fake_stats(limit=4096)) == 4096
        assert memwatch_lib.hbm_budget_bytes(
            None, stats_fn=lambda: {}) is None


# -- OOM preflight ----------------------------------------------------------

def _inputs(n=8):
    return CostInputs(flops=1e12, hbm_bytes=1e9,
                      dense_grad_bytes=int(1e8),
                      table_grad_bytes=int(1e8), num_devices=n)


class TestOOMPreflight:
    def _search(self, **cfg_kw):
        cfg = TuneConfig(top_k=2, run_options=("HYBRID",),
                         trial_steps=2, trial_warmup=0, **cfg_kw)
        return MeshSearch(8, cfg, Plan(1, 8, "HYBRID"))

    def test_refused_plan_never_trials_and_is_recorded(self):
        ms = self._search(hbm_budget_gb=1.0, hbm_headroom=0.5)
        scored_order = []

        def preflight(plan):
            scored_order.append(plan.describe())
            # refuse exactly the first (best-scored) candidate
            return int(10e9) if len(scored_order) == 1 else 1000

        ms.set_preflight(preflight)
        first = ms.begin(_inputs())
        # the refused front-runner is NOT the first trial
        assert first.describe() != scored_order[0]
        nxt = first
        while nxt is not None:
            nxt = ms.report(nxt, 0.01)
        s = ms.summary()
        assert s["pruned_oom"] == 1
        assert s["oom_refusals"][0]["plan"] == scored_order[0]
        assert s["oom_refusals"][0]["compiled_peak_bytes"] == \
            int(10e9)
        assert s["hbm_budget_bytes"] == int(1e9)
        assert s["hbm_headroom"] == 0.5
        # the refused plan was never measured
        trialed = {t["plan"] for t in s["trials"]}
        assert scored_order[0] not in trialed
        # accounting stays consistent: every scored plan is trialed,
        # cost-pruned or OOM-refused
        assert (len(s["trials"]) + s["pruned_by_cost_model"]
                + s["pruned_oom"]) == len(s["scored"])

    def test_all_refused_raises_loudly(self):
        ms = self._search(hbm_budget_gb=1.0)
        ms.set_preflight(lambda plan: int(10e9))
        with pytest.raises(RuntimeError, match="exceeds the HBM"):
            ms.begin(_inputs())

    def test_no_budget_skips_preflight(self):
        # CPU rig, no override: the preflight must not guess
        ms = self._search()
        ms.set_preflight(lambda plan: int(10e9))
        ms.begin(_inputs())
        s = ms.summary()
        assert s["pruned_oom"] == 0
        assert s["hbm_budget_bytes"] is None

    def test_unknowable_peak_passes(self):
        ms = self._search(hbm_budget_gb=1.0)
        ms.set_preflight(lambda plan: None)
        first = ms.begin(_inputs())
        assert first is not None
        assert ms.summary()["pruned_oom"] == 0


def test_session_preflight_refusal_in_tune_decision(rng, tmp_path,
                                                    monkeypatch):
    """Acceptance pin: a plan whose compiled peak exceeds the HBM
    budget is refused before any measured trial, and the refusal
    appears in tune_summary() AND the tune_decision flight
    artifact."""
    import jax.numpy as jnp
    import optax

    from parallax_tpu.core import mesh as mesh_lib
    from parallax_tpu.ops import embedding as emb_ops

    def fake_compiled_step_memory(engine):
        # every sharded plan "needs" 10GB; only the replicated tp=1
        # plan fits the 1GB budget
        shards = mesh_lib.num_shards(engine.mesh)
        return {"peak_bytes": 1000 if shards == 1 else int(10e9),
                "basis": "test"}

    monkeypatch.setattr(memwatch_lib, "compiled_step_memory",
                        fake_compiled_step_memory)

    def init_fn(rng_):
        import jax
        return {"emb": jax.random.normal(rng_, (64, 8)) * 0.1}

    def loss_fn(params, batch):
        rows = emb_ops.embedding_lookup(params["emb"], batch["ids"])
        return jnp.mean(rows ** 2)

    model = parallax.Model(init_fn, loss_fn,
                           optimizer=optax.sgd(0.1))
    sess, *_ = parallax.parallel_run(
        model,
        parallax_config=parallax.Config(
            run_option="HYBRID", search_partitions=False,
            eager_fetch=True, flight_dir=str(tmp_path),
            tune_config=TuneConfig(
                top_k=2, run_options=("HYBRID",), trial_steps=2,
                trial_warmup=0, hbm_budget_gb=1.0)))
    try:
        feed = {"ids": rng.integers(0, 64, (16,)).astype(np.int32)}
        for _ in range(12):
            float(sess.run("loss", feed_dict=feed))
            if sess._search is None:
                break
        assert sess._search is None, "search should settle"
        s = sess.tune_summary()
        assert s["pruned_oom"] >= 1, s
        refused = {r["plan"] for r in s["oom_refusals"]}
        trialed = {t["plan"] for t in s["trials"]}
        assert refused and not (refused & trialed)
        # only the replicated plan fits -> it is the winner
        assert s["winner"]["plan"].startswith("dp8xtp1")
        # the refusal rides the tune_decision flight artifact
        art = [p for p in sess.flight.dump_paths
               if "tune_decision" in p]
        assert art, sess.flight.dump_paths
        doc = json.loads(open(art[0]).read())
        assert doc["detail"]["pruned_oom"] >= 1
        assert doc["detail"]["oom_refusals"][0]["plan"] in refused
    finally:
        sess.close()


# -- session profile window (in-process) ------------------------------------

def test_session_profile_window_and_gauges(tmp_path):
    from parallax_tpu.models import simple

    sess, *_ = parallax.parallel_run(
        simple.build_model(learning_rate=0.1),
        parallax_config=parallax.Config(
            run_option="AR", search_partitions=False,
            eager_fetch=True, flight_dir=str(tmp_path)))
    try:
        rng_ = np.random.default_rng(0)
        feed = simple.make_batch(rng_, 64)
        sess.prepare(feed)
        sess.warmup(batch_sizes=[64])
        for _ in range(3):
            sess.run("loss", feed_dict=feed)
        outdir = sess.profile_steps(3)
        assert outdir is not None
        # gauges exist but are null before any parse
        assert sess.metrics_snapshot()[
            "profile.attribution_coverage"] is None
        for _ in range(3):
            sess.run("loss", feed_dict=feed)
        a = sess.profile_summary()
        assert a and not a.get("error"), a
        assert a["steps"] == 3
        assert a["coverage"] is not None and a["coverage"] > 0.5
        assert a["residual_ms"] >= 0
        assert a["by_category"]["collective"]["self_ms"] > 0
        snap = sess.metrics_snapshot()
        assert snap["profile.attribution_coverage"] == a["coverage"]
        assert snap["profile.share.collective"] == \
            a["by_category"]["collective"]["share"]
        # the flight artifact carries the parsed attribution
        path = sess.dump_flight(str(tmp_path / "dump.json"))
        doc = json.loads(open(path).read())
        assert doc["profile"]["coverage"] == a["coverage"]
        assert "memwatch" in doc
    finally:
        sess.close()


def test_write_calibration_unapplies_loaded_ratios():
    """Review pin: recalibrating while a calibration file is LOADED
    must compare the NOMINAL prediction against the measured world —
    ratios derived from already-calibrated terms would oscillate
    between generations."""
    from parallax_tpu.session import ParallaxSession

    applied = {"on_chip": 10.0, "wire": 100.0}
    # a scored entry whose terms were divided by `applied` at predict
    # time (nominal on_chip=1.0s, wire=0.5s)
    entry = {"plan": "dp8xtp1/HYBRID",
             "terms_ms": {"compute_s": 100.0, "hbm_s": 50.0,
                          "wire_dense_s": 5.0,
                          "wire_zero_shard_s": 0.0,
                          "wire_table_s": 0.0,
                          "wire_hidden_s": 0.0},
             "calibration": dict(applied)}
    sess = ParallaxSession.__new__(ParallaxSession)  # no jax setup
    sess._tune_result = {
        "winner": {"plan": entry["plan"]}, "scored": [entry],
        "cost_basis": "calibrated(nominal)"}
    sess._profile_attrib = xprof.attribute(_golden(),
                                           steps=2).as_dict()
    sess._profile_pending = None
    sess._config = parallax.Config(search_partitions=False)
    path = __import__("tempfile").mktemp(suffix=".json")
    try:
        sess.write_calibration(path)
        rec = calibrate.load(path)
        # predicted side is back at NOMINAL seconds: 0.1*10=1.0 on
        # chip, 0.005*100=0.5 wire — not the calibrated 0.1/0.005
        assert rec["terms"]["on_chip"]["predicted_s"] == \
            pytest.approx(1.0)
        assert rec["terms"]["wire"]["predicted_s"] == \
            pytest.approx(0.5)
    finally:
        if os.path.exists(path):
            os.remove(path)


def test_compiled_step_memory_refreshes_after_warmup():
    """Review pin: a preflight-time single-bucket memo must not mask
    the warmup max-across-buckets peak."""
    class FakeCompiled:
        def __init__(self, peak):
            self._p = peak

        def memory_analysis(self):
            class MA:
                temp_size_in_bytes = self._p
                argument_size_in_bytes = 0
                output_size_in_bytes = 0
                alias_size_in_bytes = 0
                generated_code_size_in_bytes = 0
            return MA()

    class FakeEngine:
        pass

    eng = FakeEngine()
    eng._executables = {"sig_small": FakeCompiled(100)}
    m1 = memwatch_lib.compiled_step_memory(eng)
    assert m1["peak_bytes"] == 100
    # memo hit while nothing changed
    assert memwatch_lib.compiled_step_memory(eng) is m1
    # warmup adds a bigger bucket: the account must refresh
    eng._executables["sig_big"] = FakeCompiled(5000)
    m2 = memwatch_lib.compiled_step_memory(eng)
    assert m2["peak_bytes"] == 5000
    assert m2["executables"] == 2


def test_gated_profile_steps_allocates_no_tempdir(monkeypatch):
    """Review pin: a worker the gating excludes must not leak one
    abandoned temp dir per profile_steps call."""
    from parallax_tpu.common.config import ProfileConfig
    from parallax_tpu.session import ParallaxSession
    import tempfile as _tf

    calls = []
    monkeypatch.setattr(
        _tf, "mkdtemp",
        lambda **kw: calls.append(kw) or "/tmp/should-not-exist")
    sess = ParallaxSession.__new__(ParallaxSession)
    sess._config = parallax.Config(
        search_partitions=False,
        profile_config=ProfileConfig(profile_worker=3))
    from parallax_tpu.profiler import ProfileHook
    sess._profile = ProfileHook(sess._config.profile_config,
                                worker_id=0)
    sess._host_step = 0
    assert sess.profile_steps(4) is None
    assert calls == []


def test_profile_steps_worker_gating():
    from parallax_tpu.common.config import ProfileConfig
    from parallax_tpu.profiler import ProfileHook
    hook = ProfileHook(ProfileConfig(profile_worker=3), worker_id=0)
    assert hook.request_window(0, 4, "/tmp/nope") is False
    hook2 = ProfileHook(ProfileConfig(profile_worker=0), worker_id=0)
    assert hook2.request_window(0, 4, "/tmp/yes") is True
    with pytest.raises(RuntimeError):
        hook2.request_window(0, 4, "/tmp/again")


# -- the tier-1 acceptance guard (subprocess) -------------------------------

def test_profile_attribution_guard():
    """ISSUE 13 acceptance: >= 90% of the measured device step wall
    attributed on the tier-1 CPU backend, residual explicit,
    taxonomy + dense/sparse split live, calibration round-trip —
    asserted end to end in a subprocess (check_serve_slo pattern)."""
    env = dict(os.environ,
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(os.path.dirname(__file__), "..")]
                   + ([os.environ["PYTHONPATH"]]
                      if os.environ.get("PYTHONPATH") else [])),
               JAX_PLATFORMS="cpu")
    cmd = [sys.executable,
           os.path.join(os.path.dirname(__file__), "..", "tools",
                        "check_profile_attrib.py")]
    last = None
    for _ in range(2):
        proc = subprocess.run(cmd, env=env, capture_output=True,
                              text=True, timeout=300)
        start = proc.stdout.find("{")
        assert start >= 0, (proc.returncode, proc.stdout[-300:],
                            proc.stderr[-500:])
        last = json.loads(proc.stdout[start:])
        if proc.returncode == 0:
            break
    assert last["ok"], last
    assert last["attribution_coverage"] >= 0.90
    assert last["residual_ms"] >= 0
    assert last["dense_sparse"]["sparse_self_ms"] > 0
    assert last["calibration"][
        "wire_predicted_over_measured"] > 0
    assert last["memwatch"]["compiled_peak_bytes"] > 0
