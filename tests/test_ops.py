"""Ops observatory (ISSUE 20): event journal (causal ring + rotating
JSONL sink), goodput/badput ledger (sum-to-wall by construction,
rollback refunds, cross-attempt persistence through checkpoint
extras), the declarative alert engine lifecycle under fake clocks
(threshold / burn-rate / absence, for_s, dedup, cooldown, resolve,
guards), the Prometheus ``parallax_alerts`` surface, flight-dump
integration, ops_report reconstruction, and the chaos guard
(tools/check_goodput.py) end to end."""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import parallax_tpu as parallax
from parallax_tpu import obs
from parallax_tpu.models import simple
from parallax_tpu.obs.alerts import (AlertEngine, AlertRule,
                                     builtin_rules)
from parallax_tpu.obs.export import render_prometheus
from parallax_tpu.obs.goodput import (BADPUT_CLASSES, GoodputLedger,
                                      dominant_badput, step_goodput)
from parallax_tpu.obs.journal import EventJournal, read_journal
from parallax_tpu.obs.metrics import MetricsRegistry


class FakeClock:
    def __init__(self, t: float = 0.0):
        self.t = t

    def __call__(self) -> float:
        return self.t


def _engine(reg, *rules, clock=None, **kw):
    return AlertEngine(reg, rules=tuple(rules),
                       clock=clock or FakeClock(), **kw)


# -- event journal ---------------------------------------------------------


class TestEventJournal:
    def test_seq_is_causal_and_ring_bounded(self):
        j = EventJournal(capacity=4, registry=MetricsRegistry())
        for i in range(10):
            j.emit("t", "tick", i=i)
        assert j.seq == 10
        ring = j.events()
        assert len(ring) == 4  # bounded
        seqs = [e["seq"] for e in ring]
        assert seqs == sorted(seqs) == [7, 8, 9, 10]
        # tail returns oldest-first copies
        tail = j.tail(2)
        assert [e["seq"] for e in tail] == [9, 10]
        tail[0]["seq"] = -1
        assert j.events()[-2]["seq"] == 9  # copy, not alias

    def test_event_envelope_and_correlation_ids(self):
        j = EventJournal(registry=MetricsRegistry())
        e = j.emit("ckpt", "save", severity="warning",
                   incident_id="inc-1", request_id="r9", step=4)
        assert e["subsystem"] == "ckpt" and e["kind"] == "save"
        assert e["severity"] == "warning"
        assert e["incident_id"] == "inc-1"
        assert e["request_id"] == "r9"
        assert e["fields"] == {"step": 4}
        # unknown severities normalize instead of poisoning the stream
        assert j.emit("t", "x", severity="catastrophic")["severity"] \
            == "info"
        # a payload field named `kind` must not collide with the
        # envelope (subsystem/kind are positional-only)
        e2 = j.emit("anomaly", "spike", kind="loss")
        assert e2["kind"] == "spike"
        assert e2["fields"]["kind"] == "loss"

    def test_jsonl_sink_and_rotation(self, tmp_path):
        p = str(tmp_path / "j.jsonl")
        j = EventJournal(capacity=8, path=p, max_bytes=600,
                         registry=MetricsRegistry())
        for i in range(20):
            j.emit("t", "tick", i=i, pad="x" * 40)
        assert os.path.exists(p + ".1")  # rotated
        # the live file holds a readable suffix of the stream
        evs = read_journal(p)
        assert evs and evs[-1]["fields"]["i"] == 19
        assert all(e["subsystem"] == "t" for e in evs)

    def test_read_journal_skips_garbage(self, tmp_path):
        p = str(tmp_path / "j.jsonl")
        with open(p, "w") as f:
            f.write('{"seq": 2, "ts": 5.0, "kind": "b"}\n')
            f.write("NOT JSON AT ALL\n")
            f.write('{"seq": 1, "ts": 4.0, "kind": "a"}\n')
        evs = read_journal(p)
        assert [e["kind"] for e in evs] == ["a", "b"]  # ts-ordered
        assert read_journal(str(tmp_path / "missing.jsonl")) == []

    def test_killswitch_emit_is_noop(self):
        j = EventJournal(registry=MetricsRegistry())
        j.emit("t", "kept")
        obs.disable()
        try:
            assert j.emit("t", "dropped") is None
        finally:
            obs.enable()
        assert j.seq == 1
        assert [e["kind"] for e in j.events()] == ["kept"]

    def test_non_json_fields_degrade_not_kill(self, tmp_path):
        p = str(tmp_path / "j.jsonl")
        j = EventJournal(path=p, registry=MetricsRegistry())
        j.emit("t", "np", value=np.float32(1.5), arr=np.arange(2))
        assert len(read_journal(p)) == 1  # stringified, not lost

    def test_validation(self):
        with pytest.raises(ValueError):
            EventJournal(capacity=0)
        with pytest.raises(ValueError):
            EventJournal(max_bytes=0)


# -- goodput ledger --------------------------------------------------------


def _row(step, wall_ms, data_wait_ms=0.0):
    return {"step": step, "wall_ms": wall_ms,
            "data_wait_ms": data_wait_ms}


class TestGoodputLedger:
    def test_step_partition_and_sum_to_wall(self):
        led = GoodputLedger(MetricsRegistry())
        led.on_step(_row(0, 100.0, data_wait_ms=10.0))
        led.on_step(_row(1, 50.0))
        acct = led.account()
        assert acct["steps"] == 2
        assert acct["productive_s"] == pytest.approx(0.14)
        assert acct["badput_s"]["data_wait"] == pytest.approx(0.01)
        # the invariant: productive + sum(badput incl unattributed)
        # == wall EXACTLY, because unattributed is the remainder
        total = acct["productive_s"] + sum(acct["badput_s"].values())
        assert total == pytest.approx(acct["wall_s"], abs=1e-6)
        assert set(BADPUT_CLASSES) <= set(acct["badput_s"])

    def test_note_badput_carve_moves_not_adds(self):
        led = GoodputLedger(MetricsRegistry())
        led.on_step(_row(0, 1000.0))
        led.note_badput("ckpt_stall", 0.3, carve_from_productive=True)
        acct = led.account()
        assert acct["productive_s"] == pytest.approx(0.7)
        assert acct["badput_s"]["ckpt_stall"] == pytest.approx(0.3)
        with pytest.raises(ValueError):
            led.note_badput("no_such_class", 1.0)

    def test_rollback_refunds_measured_time(self):
        led = GoodputLedger(MetricsRegistry())
        for s in range(6):
            led.on_step(_row(s, 100.0))
        # snapshot step 4 (post-increment numbering): steps 4 and 5
        # are the rewound work
        moved = led.on_rollback(4)
        assert moved == pytest.approx(0.2)
        acct = led.account()
        assert acct["badput_s"]["rollback_discarded"] \
            == pytest.approx(0.2)
        assert acct["productive_s"] == pytest.approx(0.4)
        # a second rollback to the same step moves nothing new
        assert led.on_rollback(4) == 0.0

    def test_run_epoch_anchors_startup_as_compile_warmup(self):
        led = GoodputLedger(MetricsRegistry(),
                            run_epoch=time.time() - 30.0)
        acct = led.account()
        assert acct["badput_s"]["compile_warmup"] \
            == pytest.approx(30.0, abs=2.0)
        assert acct["wall_s"] >= 30.0

    def test_restore_spans_attempts_and_books_the_gap(self):
        led1 = GoodputLedger(MetricsRegistry(),
                             run_epoch=time.time() - 10.0)
        led1.on_step(_row(0, 2000.0))
        snap = led1.snapshot()
        assert snap["attempts"] == 1
        # fake a 5s eviction gap before the next attempt's anchor
        snap["saved_at"] = time.time() - 5.0
        led2 = GoodputLedger(MetricsRegistry(),
                             run_epoch=time.time())
        led2.restore_snapshot(snap, restore_s=0.25, replay_s=0.05)
        acct = led2.account()
        assert acct["attempts"] == 2
        assert acct["steps"] == 1
        assert acct["badput_s"]["eviction_downtime"] \
            == pytest.approx(5.0, abs=1.0)
        assert acct["badput_s"]["restore_replay"] \
            == pytest.approx(0.30)
        # the gap joined the cumulative wall too: wall ~= attempt1's
        # 10s + 5s gap + this attempt's epsilon, and still sums
        assert acct["wall_s"] == pytest.approx(15.0, abs=1.5)
        total = acct["productive_s"] + sum(acct["badput_s"].values())
        assert total == pytest.approx(acct["wall_s"], abs=1e-6)

    def test_killswitch_on_step_is_noop(self):
        led = GoodputLedger(MetricsRegistry())
        obs.disable()
        try:
            led.on_step(_row(0, 100.0))
            led.note_badput("data_wait", 1.0)
            assert led.on_rollback(0) == 0.0
        finally:
            obs.enable()
        acct = led.account()
        assert acct["steps"] == 0
        assert sum(v for k, v in acct["badput_s"].items()
                   if k != "unattributed") == 0.0

    def test_dominant_badput(self):
        assert dominant_badput({"badput_s": {}}) is None
        assert dominant_badput(
            {"badput_s": {"data_wait": 0.0}}) is None
        assert dominant_badput(
            {"badput_s": {"data_wait": 1.0,
                          "ckpt_stall": 3.0}}) == "ckpt_stall"

    def test_timeline_goodput_delegates_to_step_goodput(self):
        tl = obs.StepTimeline(MetricsRegistry(), capacity=16)
        for s in range(4):
            tl.record_step(s, 0.0, 1e-3, 1e-4, 1e-4, 1e-4, 5e-4, 0.0)
        # single owner of the math: the method and the function agree
        # key for key
        assert tl.goodput() == step_goodput(tl)
        assert tl.goodput()["steps"] == 4
        assert "phase_frac" in tl.goodput()


# -- alert engine ----------------------------------------------------------


class TestAlertRule:
    def test_validation(self):
        with pytest.raises(ValueError):
            AlertRule("x", "m", kind="nope")
        with pytest.raises(ValueError):
            AlertRule("x", "m", op="!=")
        with pytest.raises(ValueError):
            AlertRule("x", "m", kind="burn_rate", window_s=0)

    def test_builtin_rules_cover_the_stock_signals(self):
        rules = {r.name: r for r in builtin_rules(goodput_floor=0.4)}
        assert rules["slo_burn"].metric \
            == "serve.slo.deadline_miss_budget_consumed"
        assert rules["instability"].metric == "health.instability"
        assert rules["serve_recompiles"].kind == "burn_rate"
        assert rules["page_pool_exhausted"].metric \
            == "serve.kv_refill_deferred"
        gf = rules["goodput_floor"]
        assert gf.threshold == 0.4 and gf.op == "<"
        assert gf.guard_metric == "ops.wall_s"  # no early-run flap


class TestAlertEngine:
    def test_threshold_lifecycle_pending_firing_resolved(self):
        reg = MetricsRegistry()
        clk = FakeClock()
        eng = _engine(reg, AlertRule("hot", "g", op=">",
                                     threshold=5.0, for_s=10.0,
                                     cooldown_s=0.0), clock=clk)
        g = reg.gauge("g")
        g.set(1.0)
        assert eng.evaluate() == [] and eng.state("hot") == "ok"
        g.set(9.0)
        clk.t = 100.0
        assert eng.evaluate() == []  # breach not yet sustained
        assert eng.state("hot") == "pending"
        clk.t = 111.0
        fired = eng.evaluate()
        assert [e["transition"] for e in fired] == ["firing"]
        assert eng.state("hot") == "firing"
        assert eng.active() == ["hot"]
        # dedup: still breached -> no re-emission
        clk.t = 112.0
        assert eng.evaluate() == []
        g.set(1.0)
        clk.t = 113.0
        assert [e["transition"] for e in eng.evaluate()] \
            == ["resolved"]
        assert eng.state("hot") == "ok" and eng.active() == []

    def test_cooldown_suppresses_the_refire_flap(self):
        reg = MetricsRegistry()
        clk = FakeClock()
        eng = _engine(reg, AlertRule("flap", "g", op=">",
                                     threshold=0.5, for_s=0.0,
                                     cooldown_s=30.0), clock=clk)
        g = reg.gauge("g")
        g.set(1.0)
        clk.t = 1.0
        assert len(eng.evaluate()) == 1  # fires
        g.set(0.0)
        clk.t = 2.0
        assert len(eng.evaluate()) == 1  # resolves
        g.set(1.0)
        clk.t = 3.0
        assert eng.evaluate() == []  # inside cooldown: suppressed
        clk.t = 40.0
        assert [e["transition"] for e in eng.evaluate()] == ["firing"]
        assert eng.summary()["firings_total"] == 2

    def test_burn_rate_fires_on_counter_slope(self):
        reg = MetricsRegistry()
        clk = FakeClock()
        eng = _engine(reg, AlertRule("burn", "c", kind="burn_rate",
                                     op=">", threshold=0.5,
                                     window_s=60.0, cooldown_s=0.0),
                      clock=clk)
        c = reg.counter("c")
        clk.t = 0.0
        assert eng.evaluate() == []  # one sample: no slope yet
        clk.t = 10.0
        assert eng.evaluate() == []  # flat: rate 0
        c.inc(100)
        clk.t = 20.0
        assert [e["transition"] for e in eng.evaluate()] == ["firing"]
        # flat again long enough for the window to forget the spike
        clk.t = 90.0
        assert [e["transition"] for e in eng.evaluate()] \
            == ["resolved"]

    def test_absence_fires_until_the_metric_appears(self):
        reg = MetricsRegistry()
        clk = FakeClock()
        eng = _engine(reg, AlertRule("dead", "heartbeat",
                                     kind="absence", cooldown_s=0.0),
                      clock=clk)
        clk.t = 1.0
        assert [e["transition"] for e in eng.evaluate()] == ["firing"]
        reg.gauge("heartbeat").set(1.0)
        clk.t = 2.0
        assert [e["transition"] for e in eng.evaluate()] \
            == ["resolved"]

    def test_guard_metric_gates_until_signal(self):
        reg = MetricsRegistry()
        clk = FakeClock()
        eng = _engine(reg, AlertRule("floor", "frac", op="<",
                                     threshold=0.5,
                                     guard_metric="wall",
                                     guard_min=100.0,
                                     cooldown_s=0.0), clock=clk)
        reg.gauge("frac").set(0.01)  # would breach
        reg.gauge("wall").set(5.0)   # but the run is too young
        clk.t = 1.0
        assert eng.evaluate() == []
        reg.gauge("wall").set(200.0)
        clk.t = 2.0
        assert [e["transition"] for e in eng.evaluate()] == ["firing"]

    def test_dotted_metric_resolves_into_summary(self):
        reg = MetricsRegistry()
        clk = FakeClock()
        h = reg.histogram("lat_ms")
        for v in (1.0, 2.0, 100.0):
            h.record(v)
        eng = _engine(reg, AlertRule("p", "lat_ms.max", op=">",
                                     threshold=50.0, cooldown_s=0.0),
                      clock=clk)
        clk.t = 1.0
        assert [e["transition"] for e in eng.evaluate()] == ["firing"]

    def test_transitions_land_in_journal_and_flight(self, tmp_path):
        reg = MetricsRegistry()
        clk = FakeClock()
        j = EventJournal(registry=reg)

        class SpyFlight:
            def __init__(self):
                self.triggers = []

            def trigger(self, reason, detail):
                self.triggers.append((reason, detail))

        fl = SpyFlight()
        eng = AlertEngine(reg, rules=(AlertRule(
            "hot", "g", op=">", threshold=0.5, cooldown_s=0.0,
            severity="error"),), journal=j, flight=fl, clock=clk)
        reg.gauge("g").set(1.0)
        clk.t = 1.0
        eng.evaluate()
        ev = [e for e in j.events() if e["subsystem"] == "alert"]
        assert ev and ev[-1]["kind"] == "firing"
        assert ev[-1]["severity"] == "error"
        assert ev[-1]["fields"]["alert"] == "hot"
        assert fl.triggers and fl.triggers[0][0] == "alert:hot"
        reg.gauge("g").set(0.0)
        clk.t = 2.0
        eng.evaluate()
        assert [e["kind"] for e in j.events()
                if e["subsystem"] == "alert"] == ["firing", "resolved"]
        # resolve does NOT re-dump flight
        assert len(fl.triggers) == 1

    def test_prometheus_alert_rows(self):
        reg = MetricsRegistry()
        clk = FakeClock()
        eng = _engine(reg,
                      AlertRule("hot", "g", op=">", threshold=0.5,
                                cooldown_s=0.0, severity="error"),
                      AlertRule("cold", "g", op="<", threshold=-1.0,
                                cooldown_s=0.0), clock=clk)
        reg.gauge("g").set(1.0)
        clk.t = 1.0
        eng.evaluate()
        rows = eng.prometheus_alerts()
        by_name = {r["alert"]: r for r in rows}
        assert by_name["hot"]["state"] == "firing"
        assert by_name["hot"]["value"] == 1.0
        assert by_name["cold"]["value"] == 0.0
        text = render_prometheus({"": reg.snapshot()}, alerts=rows)
        assert 'parallax_alerts{alert="hot",severity="error",' \
               'state="firing"} 1.0' in text
        # the engine's own counters surface too
        assert "parallax_alerts_firings 1.0" in text

    def test_poll_throttles_and_thread_start_stop(self):
        reg = MetricsRegistry()
        clk = FakeClock()
        eng = AlertEngine(reg, rules=(AlertRule(
            "hot", "g", op=">", threshold=0.5, cooldown_s=0.0),),
            interval_s=30.0, clock=clk)
        reg.gauge("g").set(1.0)
        clk.t = 1.0
        eng.poll()  # first poll evaluates
        assert eng.state("hot") == "firing"
        reg.gauge("g").set(0.0)
        clk.t = 10.0
        eng.poll()  # inside the interval: no pass
        assert eng.state("hot") == "firing"
        clk.t = 40.0
        eng.poll()
        assert eng.state("hot") == "ok"
        # daemon thread: starts, evaluates, stops cleanly
        eng2 = AlertEngine(reg, rules=(), interval_s=0.01)
        eng2.start()
        time.sleep(0.05)
        eng2.stop()
        assert int(reg.snapshot()["alerts.evals"]) >= 1

    def test_evaluate_never_raises_on_poisoned_gauge(self):
        reg = MetricsRegistry()

        def boom():
            raise RuntimeError("poisoned")

        reg.gauge("bad").set_fn(boom)
        eng = _engine(reg, AlertRule("x", "bad", op=">",
                                     threshold=0.0))
        assert eng.evaluate() == []  # snapshot failure swallowed

    def test_killswitch_structural_noop(self):
        reg = MetricsRegistry()
        clk = FakeClock()
        eng = _engine(reg, AlertRule("hot", "g", op=">",
                                     threshold=0.5, cooldown_s=0.0),
                      clock=clk)
        reg.gauge("g").set(1.0)
        obs.disable()
        try:
            clk.t = 1.0
            assert eng.evaluate() == []
            eng.poll()
        finally:
            obs.enable()
        assert eng.state("hot") == "ok"

    def test_clean_session_fires_no_builtin_alert(self):
        # the builtin ruleset over a healthy training registry: no
        # serve metrics, low instability, guarded goodput floor
        reg = MetricsRegistry()
        reg.gauge("health.instability").set(0.1)
        reg.gauge("ops.goodput_fraction").set(0.05)  # early-run low
        reg.gauge("ops.wall_s").set(30.0)            # ...but young
        clk = FakeClock()
        eng = AlertEngine(reg, rules=builtin_rules(), clock=clk)
        for t in (1.0, 50.0, 100.0):
            clk.t = t
            assert eng.evaluate() == []
        assert eng.active() == []


# -- session integration ---------------------------------------------------


def _session(**cfg_kw):
    sess, *_ = parallax.parallel_run(
        simple.build_model(learning_rate=0.1),
        parallax_config=parallax.Config(run_option="AR",
                                        search_partitions=False,
                                        **cfg_kw))
    return sess


class TestSessionIntegration:
    def test_ledger_persists_across_ckpt_save_restore(self, tmp_path):
        ck = str(tmp_path / "ck")
        rng = np.random.default_rng(0)
        sess = _session(ckpt_config=parallax.CheckPointConfig(
            ckpt_dir=ck, save_ckpt_steps=2))
        for i in range(4):
            sess.run(feed_dict=simple.make_batch(rng, 32))
        acct1 = sess.ops_account()
        assert acct1["attempts"] == 1 and acct1["steps"] == 4
        sess.close()
        # a second session on the same ckpt_dir restores the manifest
        # extras: the ledger continues the account as attempt 2
        sess2 = _session(ckpt_config=parallax.CheckPointConfig(
            ckpt_dir=ck, save_ckpt_steps=2))
        sess2.prepare(simple.make_batch(rng, 32))
        try:
            acct2 = sess2.ops_account()
            assert acct2["attempts"] == 2
            assert acct2["steps"] >= 4  # attempt 1's steps adopted
            assert acct2["badput_s"]["restore_replay"] > 0
            total = acct2["productive_s"] \
                + sum(acct2["badput_s"].values())
            assert total == pytest.approx(acct2["wall_s"], abs=1e-4)
        finally:
            sess2.close()

    def test_flight_dump_embeds_journal_ops_alerts(self, tmp_path):
        sess = _session(journal_path=str(tmp_path / "j.jsonl"))
        rng = np.random.default_rng(0)
        try:
            sess.run(feed_dict=simple.make_batch(rng, 32))
            sess.journal.emit("test", "marker", note="breadcrumb")
            path = sess.dump_flight(path=str(tmp_path / "f.json"))
            with open(path) as f:
                doc = json.load(f)
            tail = doc["journal_tail"]
            assert any(e["kind"] == "marker" for e in tail)
            assert doc["ops"]["wall_s"] > 0
            assert "goodput_fraction" in doc["ops"]
            assert doc["alerts"]["rules"] >= 5  # builtins armed
            assert doc["alerts"]["firing"] == []
            # the dump itself journaled, carrying its incident id
            ev = [e for e in sess.journal.events()
                  if e["subsystem"] == "flight"]
            assert ev and ev[-1]["incident_id"] == doc["incident_id"]
        finally:
            sess.close()

    def test_session_close_journals_and_stops_alerts(self, tmp_path):
        jp = str(tmp_path / "j.jsonl")
        sess = _session(journal_path=jp)
        rng = np.random.default_rng(0)
        sess.run(feed_dict=simple.make_batch(rng, 32))
        sess.close()
        evs = read_journal(jp)
        assert [e for e in evs if (e["subsystem"], e["kind"])
                == ("session", "close")]

    def test_ckpt_saves_journal(self, tmp_path):
        sess = _session(
            journal_path=str(tmp_path / "j.jsonl"),
            ckpt_config=parallax.CheckPointConfig(
                ckpt_dir=str(tmp_path / "ck"), save_ckpt_steps=2))
        rng = np.random.default_rng(0)
        try:
            for i in range(4):
                sess.run(feed_dict=simple.make_batch(rng, 32))
            kinds = [(e["subsystem"], e["kind"])
                     for e in sess.journal.events()]
            assert kinds.count(("ckpt", "save")) == 2
        finally:
            sess.close()

    def test_config_validation(self):
        with pytest.raises(ValueError):
            parallax.Config(journal_capacity=0)
        with pytest.raises(ValueError):
            parallax.Config(journal_max_bytes=-1)
        with pytest.raises(ValueError):
            parallax.Config(alert_interval_s=0)
        with pytest.raises(ValueError):
            parallax.Config(goodput_floor=1.5)


# -- ops_report ------------------------------------------------------------


class TestOpsReport:
    def test_build_report_and_render(self, tmp_path):
        sys.path.insert(0, os.path.join(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__)))))
        from tools.ops_report import build_report, render_text
        events = [
            {"seq": 1, "ts": 1.0, "subsystem": "ckpt",
             "kind": "save", "severity": "info"},
            {"seq": 2, "ts": 2.0, "subsystem": "alert",
             "kind": "firing", "severity": "error",
             "fields": {"alert": "hot"}},
            {"seq": 3, "ts": 3.0, "subsystem": "flight",
             "kind": "dump", "severity": "warning",
             "incident_id": "inc-7"},
            # a resumed attempt: seq restarts at 1
            {"seq": 1, "ts": 10.0, "subsystem": "ckpt",
             "kind": "restored", "severity": "info"},
        ]
        account = {"wall_s": 100.0, "goodput_fraction": 0.7,
                   "steps": 10, "attempts": 2,
                   "badput_s": {"ckpt_stall": 2.0,
                                "eviction_downtime": 20.0}}
        rep = build_report(events, account)
        assert rep["events"] == 4
        assert rep["attempts_in_journal"] == 2
        assert rep["incident_ids"] == ["inc-7"]
        assert rep["unresolved_alerts"] == ["hot"]
        assert rep["dominant_badput"] == "eviction_downtime"
        text = render_text(events, account, rep)
        assert "eviction_downtime" in text and "dominant" in text
        assert "STILL FIRING: hot" in text


# -- the chaos guard (tier-1 gate) -----------------------------------------


def test_goodput_chaos_guard():
    """tools/check_goodput.py end to end: clean run sums to the
    parent-measured wall within 5% and fires zero alerts; SIGKILL +
    resume yields one cumulative ledger spanning both attempts with
    restore_replay and eviction_downtime attributed; a NaN rollback
    books the discarded steps' measured time in its own class with
    the journal events in causal order."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.setdefault("XLA_FLAGS",
                   "--xla_force_host_platform_device_count=8")
    proc = subprocess.run(
        [sys.executable, os.path.join("tools", "check_goodput.py")],
        env=env, capture_output=True, text=True, timeout=560,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert proc.returncode == 0, (proc.stdout[-3000:]
                                  + proc.stderr[-2000:])
    result = json.loads(proc.stdout)
    assert result["ok"], result["violations"]
    assert result["clean"]["alerts_fired"] == 0
    assert result["clean"]["wall_rel_err"] <= 0.05
    assert result["sigkill"]["attempts"] == 2
    assert result["sigkill"]["wall_rel_err"] <= 0.05
    assert result["nan"]["rollback_discarded_s"] > 0
