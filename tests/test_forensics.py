"""Training forensics (ISSUE 5): step-time attribution timeline,
flight recorder dump triggers (crash / non-finite loss / serve SLO
breach / explicit), anomaly + straggler detection, and the
device-peak-FLOPs table under a TPU stub."""

import glob
import json
import time

import numpy as np
import pytest

import parallax_tpu as parallax
from parallax_tpu import obs
from parallax_tpu.common import flops as flops_lib
from parallax_tpu.common.config import AnomalyConfig
from parallax_tpu.models import simple
from parallax_tpu.obs import aggregate
from parallax_tpu.obs.anomaly import AnomalyMonitor
from parallax_tpu.obs.flightrec import FlightRecorder
from parallax_tpu.obs.metrics import MetricsRegistry
from parallax_tpu.obs.timeline import StepTimeline


def _simple_session(**cfg_kw):
    sess, *_ = parallax.parallel_run(
        simple.build_model(learning_rate=0.1),
        parallax_config=parallax.Config(run_option="AR",
                                        search_partitions=False,
                                        **cfg_kw))
    return sess


def _batches(n, batch=64, seed=0):
    rng = np.random.default_rng(seed)
    return [simple.make_batch(rng, batch) for _ in range(n)]


# -- step-time attribution (obs/timeline.py) -------------------------------


class TestStepTimeline:
    def test_rows_components_and_residual(self):
        tl = StepTimeline(MetricsRegistry(), capacity=8)
        tl.record_step(0, ts=0.0, wall_s=0.010, data_wait_s=0.002,
                       convert_s=0.001, h2d_s=0.001, dispatch_s=0.004,
                       fetch_block_s=0.001)
        (row,) = tl.rows()
        assert row["wall_ms"] == pytest.approx(10.0)
        assert row["data_wait_ms"] == pytest.approx(2.0)
        # dispatch is net of its inner h2d + fetch-block shares
        assert row["dispatch_ms"] == pytest.approx(2.0)
        attributed = (row["data_wait_ms"] + row["convert_ms"]
                      + row["h2d_ms"] + row["dispatch_ms"]
                      + row["fetch_block_ms"])
        assert row["device_est_ms"] == pytest.approx(10.0 - attributed)
        assert row["mfu"] is None  # no flops attached

    def test_ring_eviction_and_fetch_block_attribution(self):
        tl = StepTimeline(MetricsRegistry(), capacity=4)
        for s in range(10):
            tl.record_step(s, ts=float(s), wall_s=0.01,
                           dispatch_s=0.01)
        rows = tl.rows()
        assert [r["step"] for r in rows] == [6, 7, 8, 9]
        assert tl.total_rows == 10
        # lazy fetch attributed back to its (still-ringed) step
        tl.add_fetch_block(8, 0.005)
        row8 = next(r for r in tl.rows() if r["step"] == 8)
        assert row8["fetch_block_ms"] == pytest.approx(5.0)
        # an evicted step's fetch-block is dropped, not crashed on
        tl.add_fetch_block(0, 0.005)

    def test_pre_dispatch_h2d_not_subtracted_from_dispatch(self):
        """The place-batch-then-step pattern: placement paid BEFORE the
        step call counts as H2D but must not be subtracted from a
        dispatch share that never contained it."""
        tl = StepTimeline(MetricsRegistry(), capacity=4)
        tl.record_step(0, ts=0.0, wall_s=0.020, dispatch_s=0.004,
                       h2d_pre_s=0.010)
        (row,) = tl.rows()
        assert row["h2d_ms"] == pytest.approx(10.0)
        assert row["dispatch_ms"] == pytest.approx(4.0)  # not clamped

    def test_mfu_and_goodput_account(self):
        tl = StepTimeline(MetricsRegistry(), capacity=8)
        for s in range(4):
            tl.record_step(s, ts=0.0, wall_s=0.010, data_wait_s=0.002,
                           dispatch_s=0.003)
        # 1e9 FLOPs per 10ms step against a 1e12 FLOP/s peak = 0.1 MFU
        tl.set_flops(1e9, 1e12)
        rows = tl.rows()
        assert rows[-1]["mfu"] == pytest.approx(0.1)
        g = tl.goodput()
        assert g["steps"] == 4
        assert g["mfu_mean"] == pytest.approx(0.1)
        assert g["phase_frac"]["data_wait_ms"] == pytest.approx(0.2)
        fracs = sum(v for v in g["phase_frac"].values())
        assert fracs == pytest.approx(1.0, abs=1e-6)
        json.dumps(g)  # JSON-ready

    def test_registry_gauges_and_disabled_noop(self):
        reg = MetricsRegistry()
        tl = StepTimeline(reg, capacity=8)
        tl.record_step(0, ts=0.0, wall_s=0.01, dispatch_s=0.004)
        snap = reg.snapshot()
        assert snap["timeline.wall_ms"]["p50"] == pytest.approx(10.0)
        assert snap["timeline.steps"] == 1
        obs.disable()
        try:
            tl.record_step(1, ts=0.0, wall_s=0.01)
            tl.add_fetch_block(0, 1.0)
        finally:
            obs.enable()
        assert tl.total_rows == 1
        assert tl.rows()[0]["fetch_block_ms"] == 0.0


# -- anomaly detection (obs/anomaly.py) ------------------------------------


def _cfg(**kw):
    base = dict(window=32, min_samples=8, spike_mads=6.0,
                spike_min_ratio=2.0, shift_window=4, shift_ratio=1.5,
                cooldown=16)
    base.update(kw)
    return AnomalyConfig(**base)


class TestAnomaly:
    def test_spike_fires_and_counts(self):
        reg = MetricsRegistry()
        am = AnomalyMonitor(reg, _cfg())
        for i in range(20):
            assert am.observe("step_time_ms", i,
                              10.0 + 0.1 * (i % 3)) is None
        ev = am.observe("step_time_ms", 20, 200.0)
        assert ev is not None and ev.kind == "spike"
        assert ev.step == 20 and ev.ratio > 10
        assert reg.counter("anomaly.step_time_ms.spikes").value == 1
        assert am.events()[0]["signal"] == "step_time_ms"

    def test_cooldown_suppresses_repeat_firing(self):
        am = AnomalyMonitor(MetricsRegistry(), _cfg(cooldown=16))
        for i in range(20):
            am.observe("s", i, 10.0)
        assert am.observe("s", 20, 300.0) is not None
        # within cooldown: an equal outlier stays silent
        assert am.observe("s", 21, 300.0) is None

    def test_shift_detects_sustained_regression_and_rebaselines(self):
        reg = MetricsRegistry()
        am = AnomalyMonitor(reg, _cfg(spike_min_ratio=10.0))
        for i in range(30):
            am.observe("s", i, 10.0 + 0.01 * (i % 5))
        # a sustained 1.8x level change (no single sample is a spike
        # at spike_min_ratio=10): the change-point detector must fire
        fired = None
        for i in range(30, 50):
            ev = am.observe("s", i, 18.0)
            if ev is not None:
                fired = ev
                break
        assert fired is not None and fired.kind == "shift"
        # fires as soon as the recent mean crosses shift_ratio x the
        # baseline (the mean still mixes a few old-level samples)
        assert fired.ratio >= 1.5
        assert fired.baseline == pytest.approx(10.0, rel=0.05)
        assert reg.counter("anomaly.s.shifts").value == 1
        # rebaselined: the new level is now normal — no refiring even
        # after cooldown expires
        for i in range(50, 120):
            assert am.observe("s", i, 18.0) is None

    def test_stable_signal_never_fires_and_disabled_noop(self):
        am = AnomalyMonitor(MetricsRegistry(), _cfg())
        for i in range(200):
            assert am.observe("s", i, 5.0 + 0.05 * (i % 7)) is None
        obs.disable()
        try:
            n = am.total_observed
            am.observe("s", 999, 1e9)
        finally:
            obs.enable()
        assert am.total_observed == n

    def test_on_event_callback(self):
        got = []
        am = AnomalyMonitor(MetricsRegistry(), _cfg(),
                            on_event=got.append)
        for i in range(20):
            am.observe("s", i, 1.0)
        am.observe("s", 20, 50.0)
        assert len(got) == 1 and got[0].kind == "spike"

    @pytest.mark.parametrize("n", [1, 2, 3, 8, 31, 64])
    def test_sorted_mad_matches_the_sort_definition(self, n):
        """The baseline's MAD is selected from two ascending runs of
        deviations; it must equal sorting every deviation, ties and
        repeated values included."""
        from parallax_tpu.obs.anomaly import _sorted_mad
        rng = np.random.default_rng(n)
        for trial in range(200):
            if trial % 2:
                vals = rng.integers(0, 4, n).astype(float).tolist()
            else:
                vals = rng.lognormal(0.0, 1.0, n).tolist()
            vals.sort()
            med = vals[n // 2]
            want = sorted(abs(v - med) for v in vals)[n // 2]
            assert _sorted_mad(vals) == want, vals


# -- flight recorder (obs/flightrec.py) ------------------------------------


class TestFlightRecorder:
    def test_dump_sections_and_provider_isolation(self, tmp_path):
        def boom():
            raise RuntimeError("poisoned buffer")
        fr = FlightRecorder(
            flight_dir=str(tmp_path),
            providers={"good": lambda: {"x": 1}, "bad": boom})
        path = fr.dump("manual", detail={"k": "v"})
        doc = json.load(open(path))
        assert doc["reason"] == "manual"
        assert doc["detail"] == {"k": "v"}
        assert doc["good"] == {"x": 1}
        assert "RuntimeError" in doc["bad"]["_error"]
        assert doc["process_index"] == 0

    def test_trigger_requires_flight_dir_and_dedups(self, tmp_path):
        fr = FlightRecorder(flight_dir=None)
        assert fr.trigger("nonfinite_loss") is None  # not armed
        fr = FlightRecorder(flight_dir=str(tmp_path))
        p1 = fr.trigger("nonfinite_loss:a", {"step": 1})
        assert p1 is not None
        # same reason KEY: suppressed (one artifact per incident class)
        assert fr.trigger("nonfinite_loss:b", {"step": 2}) is None
        # a different incident class still dumps
        assert fr.trigger("serve_deadline_breach") is not None
        assert len(fr.dump_paths) == 2

    def test_max_dumps_cap(self, tmp_path):
        fr = FlightRecorder(flight_dir=str(tmp_path), max_dumps=2)
        assert fr.trigger("a") and fr.trigger("b")
        assert fr.trigger("c") is None
        assert len(fr.dump_paths) == 2

    def test_suppressed_dumps_counted_per_class(self, tmp_path):
        """ISSUE 12 satellite: a rate-limited trigger must leave a
        countable trace per incident class — a 9th incident of a
        class shows up in flightrec.suppressed.<class> instead of
        vanishing without record."""
        from parallax_tpu.obs.metrics import MetricsRegistry
        reg = MetricsRegistry()
        fr = FlightRecorder(flight_dir=str(tmp_path), registry=reg,
                            max_dumps=2)
        assert fr.trigger("nonfinite_loss:a") is not None
        for _ in range(3):  # same class: suppressed, counted
            assert fr.trigger("nonfinite_loss:b") is None
        assert fr.trigger("serve_deadline_breach") is not None
        assert fr.trigger("fleet_crash:r0") is None  # max_dumps cap
        snap = reg.snapshot()
        assert snap["flightrec.suppressed.nonfinite_loss"] == 3
        assert snap["flightrec.suppressed.fleet_crash"] == 1
        assert snap["flight.dumps_suppressed"] == 4  # aggregate kept
        assert snap["flight.dumps"] == 2

    def test_artifacts_carry_incident_ids(self, tmp_path):
        fr = FlightRecorder(flight_dir=str(tmp_path))
        p1 = fr.trigger("a")
        p2 = fr.trigger("b")
        id1 = json.load(open(p1))["incident_id"]
        id2 = json.load(open(p2))["incident_id"]
        assert id1 and id2 and id1 != id2
        assert fr.last_incident_id == id2


# -- straggler aggregation (obs/aggregate.py) ------------------------------


class TestAggregate:
    def test_find_stragglers(self):
        assert aggregate.find_stragglers([10, 10, 10, 10]) == []
        assert aggregate.find_stragglers([10, 31, 10, 10],
                                         factor=1.25) == [1]
        assert aggregate.find_stragglers([10]) == []  # single host
        assert aggregate.find_stragglers([10, 13, 40, 41],
                                         factor=1.3) == [2, 3]

    def test_build_report_names_the_laggard(self):
        rows = np.array([[10.0, 12.0, 50], [41.0, 52.0, 50],
                         [11.0, 13.0, 50]])
        rep = aggregate.build_report(rows, factor=1.25)
        assert rep["num_hosts"] == 3
        assert rep["stragglers"] == [1]
        assert rep["slowest"] == 1
        assert rep["hosts"][1]["straggler"] is True
        assert rep["hosts"][1]["vs_median"] == pytest.approx(
            41 / 11.0, abs=1e-3)
        line = aggregate.straggler_summary(rep)
        assert "process 1" in line
        assert aggregate.straggler_summary(
            aggregate.build_report(np.array([[10.0, 11.0, 5],
                                             [10.5, 11.0, 5]]))) is None
        json.dumps(rep)

    def test_single_process_collective(self):
        rep = aggregate.aggregate_host_step_times(
            {"mean_ms": 5.0, "p95_ms": 7.0, "steps": 12})
        assert rep["num_hosts"] == 1
        assert rep["stragglers"] == []
        assert rep["hosts"][0]["steps"] == 12


# -- session integration ---------------------------------------------------


class TestSessionForensics:
    def test_timeline_attribution_through_run_and_run_iter(self):
        sess = _simple_session()
        try:
            sess.run("loss", feed_dict=_batches(1)[0])
            (row,) = sess.timeline.rows()
            # the run() path converts + places on the dispatch thread
            assert row["convert_ms"] > 0
            assert row["h2d_ms"] > 0
            assert row["dispatch_ms"] > 0
            for r in sess.run_iter(_batches(6), "loss"):
                float(r)
            rows = sess.timeline.rows()
            assert len(rows) == 7
            # preplaced batches: H2D overlapped on the prefetch thread,
            # so the dispatch rows carry no critical-path H2D...
            assert all(r["h2d_ms"] == 0.0 for r in rows[1:])
            # ...and waiting on the prefetcher is attributed data-wait
            assert any(r["data_wait_ms"] > 0 for r in rows[1:])
            snap = sess.metrics_snapshot()
            assert snap["timeline.steps"] == 7
            assert snap["timeline.wall_ms"]["count"] == 7
        finally:
            sess.close()

    def test_explicit_dump_flight_without_flight_dir(self, tmp_path):
        sess = _simple_session()
        try:
            for b in _batches(3):
                sess.run("loss", feed_dict=b)
            path = sess.dump_flight(str(tmp_path / "post.json"))
            doc = json.load(open(path))
            assert doc["reason"] == "manual"
            assert len(doc["steps"]) == 3
            assert doc["goodput"]["steps"] == 3
            assert doc["config"]["run_option"] == "AR"
            assert doc["metrics"]["pipeline.steps"] == 3
            assert doc["progress"]["host_step"] == 3
        finally:
            sess.close()

    def test_crash_dump_on_step_exception(self, tmp_path):
        """Acceptance: a crash escaping a step leaves a post-mortem
        artifact (and the exception still propagates)."""
        sess = _simple_session(flight_dir=str(tmp_path))
        try:
            for b in _batches(2):
                sess.run("loss", feed_dict=b)
            bad = {"x": _batches(1)[0]["x"]}  # missing the 'y' feed
            with pytest.raises(Exception):
                sess.run("loss", feed_dict=bad)
            dumps = glob.glob(str(tmp_path / "flight_exception*.json"))
            assert len(dumps) == 1
            doc = json.load(open(dumps[0]))
            assert doc["reason"].startswith("exception:")
            assert doc["detail"]["step"] == 2
            assert len(doc["steps"]) == 2  # the history before death
        finally:
            sess.close()

    def test_nan_loss_triggers_flight_dump(self, tmp_path):
        """Acceptance: an injected NaN loss produces a flight artifact
        naming the step."""
        sess = _simple_session(monitor_health=True,
                               flight_dir=str(tmp_path))
        try:
            good = _batches(3)
            bad = _batches(1, seed=9)[0]
            bad["x"] = np.full_like(bad["x"], np.nan)
            for b in (good[0], good[1], bad, good[2]):
                sess.run("loss", feed_dict=b)
            sess.health.poll(block=True)
            dumps = glob.glob(str(tmp_path / "flight_nonfinite_loss*"))
            assert len(dumps) == 1
            doc = json.load(open(dumps[0]))
            assert doc["detail"]["step"] == 2
            assert doc["health"]["first_nonfinite_step"] == 2
            readings = doc["health"]["readings"]
            assert any(r["loss_finite"] is False for r in readings)
        finally:
            sess.close()

    def test_step_flops_after_warmup_feeds_timeline(self):
        sess = _simple_session()
        try:
            b = _batches(1)[0]
            sess.warmup(feed_dict=b, batch_sizes=[64])
            flops = sess.step_flops()  # cheap: AOT executable exists
            assert flops is not None and flops > 0
            # CPU: peak is None, so MFU must stay null — never faked
            sess.run("loss", feed_dict=b)
            assert sess.timeline.goodput()["flops_per_step"] == flops
            assert sess.timeline.goodput()["mfu_mean"] is None
        finally:
            sess.close()

    def test_place_batch_then_step_attributes_h2d(self):
        """Same-thread sess.place_batch -> placed step: the placement
        lands in the step's row as H2D without zeroing dispatch."""
        sess = _simple_session()
        try:
            placed = sess.place_batch(_batches(1)[0])
            (res,) = list(sess.run_iter(iter([placed]), "loss",
                                        placed=True))
            float(res)
            (row,) = sess.timeline.rows()
            assert row["h2d_ms"] > 0          # the pre-step placement
            assert row["dispatch_ms"] > 0     # not clamped to zero
        finally:
            sess.close()

    def test_step_flops_noncheap_retraces_when_no_executable(self):
        sess = _simple_session()
        try:
            sess.run("loss", feed_dict=_batches(1)[0])
            # no AOT executable: the cheap (monitoring) path refuses
            assert sess.step_flops() is None
            # the explicit path re-traces + lowers once and caches
            f = sess.step_flops(cheap_only=False)
            assert f is not None and f > 0
            assert sess.step_flops() == f  # now cached, cheap too
        finally:
            sess.close()

    def test_host_aggregation_lands_in_dump(self, tmp_path):
        sess = _simple_session()
        try:
            for b in _batches(4):
                sess.run("loss", feed_dict=b)
            rep = sess.aggregate_host_steps()
            assert rep["num_hosts"] == 1 and rep["stragglers"] == []
            doc = json.load(open(sess.dump_flight(
                str(tmp_path / "agg.json"))))
            assert doc["host_report"]["num_hosts"] == 1
        finally:
            sess.close()


# -- serve SLO breach trigger ----------------------------------------------


class TestServeSLOBreachDump:
    def test_deadline_breach_triggers_flight_dump(self, tmp_path):
        """Acceptance: a serve deadline breach produces a flight
        artifact (the queue sheds the expired request, the breach hook
        fires through the recorder)."""
        from parallax_tpu.serve import ServeSession
        from parallax_tpu.serve.batcher import DeadlineExceeded
        fr = FlightRecorder(flight_dir=str(tmp_path))
        serve = ServeSession(
            lambda params, batch: {"y": batch["x"]},
            {"w": np.zeros((1,), np.float32)},
            example_feed={"x": np.zeros((4,), np.float32)},
            config=parallax.Config(serve_config=parallax.ServeConfig(
                max_batch=2, max_wait_ms=30.0, max_queue=8)),
            flight=fr)
        try:
            req = serve.submit({"x": np.ones((4,), np.float32)},
                               deadline_ms=0.01)
            deadline = time.perf_counter() + 10.0
            while not req.done() and time.perf_counter() < deadline:
                time.sleep(0.01)
            assert req.done()
            with pytest.raises(DeadlineExceeded):
                req.result()
            # the breach hook fired a dump (queue or dispatch path)
            ok = time.perf_counter() + 5.0
            while not fr.dump_paths and time.perf_counter() < ok:
                time.sleep(0.01)
            dumps = glob.glob(
                str(tmp_path / "flight_serve_deadline_breach*"))
            assert len(dumps) == 1
            doc = json.load(open(dumps[0]))
            assert doc["detail"]["n"] >= 1
        finally:
            serve.close()


# -- device peak FLOPs under a TPU stub -----------------------------------


class TestDevicePeakFlops:
    def test_platform_gate_and_table(self):
        f = flops_lib.device_peak_flops
        assert f("cpu", "cpu") is None          # fallback: no number
        assert f("gpu", "NVIDIA H100") is None
        assert f("tpu", "TPU v4") == 275e12
        assert f("tpu", "TPU v5e") == 197e12
        assert f("tpu", "TPU v5p") == 459e12
        assert f("tpu", "TPU v6 lite") == 918e12
        # what a v5e chip reports as device_kind (PR 22 chip run)
        assert f("tpu", "TPU v5 lite") == 197e12

    def test_unknown_tpu_kind_raises(self):
        """On the TPU a kind the table does not hold is an error, not
        a null utilization carried through every consumer."""
        with pytest.raises(ValueError, match="TPU v99"):
            flops_lib.device_peak_flops("tpu", "TPU v99")
        with pytest.raises(ValueError, match="no published bf16 peak"):
            flops_lib.device_peak_flops("tpu", "")
        # off the TPU the same kind is no error: there is no peak
        assert flops_lib.device_peak_flops("cpu", "TPU v99") is None
