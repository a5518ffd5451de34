"""Fault-tolerant serving fleet (ISSUE 7): replicated engines,
health-aware router, chaos harness, live weight hot-swap.

Covers the error taxonomy (retryable declared on the exception, not
pattern-matched), request done-callbacks, the router state machine
(error-rate/heartbeat/latency probes, circuit breaker with exponential
backoff and probation) driven deterministically with explicit clocks,
the fault injector, one-shot fleet integration (failover on crash and
NaN, saturation spill, hot-swap that actually changes outputs with
zero recompiles), the autoscaler over fake replicas, the anomaly
rebaseline path for deliberate scale events, the fleet secondary
regression gates, paged-KV decode failover token identity in-process,
and the tier-1 chaos guard (tools/check_fleet_faults.py via the
established subprocess driver).
"""

import os
import sys
import threading
import time

import numpy as np
import pytest

import parallax_tpu as parallax
from parallax_tpu import ServeConfig
from parallax_tpu.core import mesh as mesh_lib
from parallax_tpu.serve import (DeadlineExceeded, FaultInjector,
                                FleetConfig, HealthPolicy,
                                PagePoolExhausted, ReplicaCrash,
                                ReplicaUnavailable, Request, Router,
                                ServeClosed, ServeError, ServeFleet,
                                ServeOverloaded, ServeSession)
from parallax_tpu.serve.router import (DEGRADED, DRAINING, EJECTED,
                                       HEALTHY)
from test_compile import _run_driver_json


# -- error taxonomy (declared, not pattern-matched) -------------------------


class TestErrorTaxonomy:
    def test_retryable_is_declared_on_the_class(self):
        assert ServeOverloaded.retryable is True
        assert ReplicaUnavailable.retryable is True
        assert PagePoolExhausted.retryable is True
        assert ReplicaCrash.retryable is True
        assert DeadlineExceeded.retryable is False
        assert ServeClosed.retryable is False
        assert ServeError.retryable is False

    def test_fatal_marks_replica_death_only(self):
        assert ReplicaCrash.fatal is True
        for exc in (ServeOverloaded, DeadlineExceeded, ServeClosed,
                    ReplicaUnavailable, PagePoolExhausted):
            assert getattr(exc, "fatal", False) is False, exc


# -- request done-callbacks -------------------------------------------------


class TestDoneCallbacks:
    def test_callback_fires_on_completion_and_failure(self):
        seen = []
        r = Request({"x": 1})
        r.add_done_callback(lambda req: seen.append(("done", req.id)))
        r._complete(42)
        assert seen == [("done", r.id)]
        r2 = Request({"x": 2})
        r2.add_done_callback(lambda req: seen.append("failed"))
        r2._fail(ServeError("boom"))
        assert seen[-1] == "failed"

    def test_callback_on_already_done_request_fires_immediately(self):
        r = Request({"x": 1})
        r._complete("y")
        seen = []
        r.add_done_callback(lambda req: seen.append(req._result))
        assert seen == ["y"]

    def test_broken_callback_does_not_break_delivery(self):
        r = Request({"x": 1})
        r.add_done_callback(lambda req: 1 / 0)
        r._complete("ok")
        assert r.result(timeout=1.0) == "ok"


# -- the router state machine (deterministic clocks) ------------------------


class _FakeSession:
    """Duck-typed replica for router/autoscaler units: no jax, no
    threads — load/heartbeat/alive set directly by the test."""

    def __init__(self, load=0.0):
        self._load = float(load)
        self.alive = True
        self.heartbeat = 0.0
        self.closed = False

    def load(self):
        return self._load

    def idle(self):
        return self._load == 0.0

    def close(self, drain=True):
        self.closed = True


def _policy(**kw):
    base = dict(window=4, min_outcomes=2, degrade_error_rate=0.25,
                eject_error_rate=0.5, recovery_idle_s=100.0,
                heartbeat_timeout_s=1.0, backoff_initial_s=1.0,
                backoff_max_s=8.0, probation_successes=2)
    base.update(kw)
    return HealthPolicy(**base)


class TestRouter:
    def test_places_least_loaded_healthy(self):
        r = Router(_policy())
        a = r.add("a", _FakeSession(load=5.0))
        b = r.add("b", _FakeSession(load=1.0))
        h = r.place()
        assert h is b
        r.done_placing(h)
        # a pending placement counts as load (drain-race accounting)
        b.session._load = 0.0
        a.session._load = 0.0
        h1 = r.place()
        h2 = r.place()
        assert {h1.rid, h2.rid} == {"a", "b"}
        r.done_placing(h1)
        r.done_placing(h2)

    def test_draining_and_excluded_take_no_placement(self):
        r = Router(_policy())
        r.add("a", _FakeSession())
        r.add("b", _FakeSession())
        r.set_draining("a", True)
        for _ in range(4):
            h = r.place()
            assert h.rid == "b"
            r.done_placing(h)
        with pytest.raises(ReplicaUnavailable):
            r.place(exclude=("b",))
        r.set_draining("a", False)
        assert r.get("a").state == HEALTHY

    def test_drain_restore_keeps_probation_debt(self):
        """A hot-swap rotation of a DEGRADED probationer must not
        launder it to HEALTHY: it comes back DEGRADED, still owing
        its probation successes, and serves them out normally."""
        r = Router(_policy())
        h = r.add("a", _FakeSession())
        r.record_error(h, ServeError("x"), now=0.0)
        r.record_error(h, ServeError("x"), now=0.0)
        h.session.heartbeat = 1.1
        r.tick(now=1.1)
        assert h.state == DEGRADED and h.probation_left == 2
        r.set_draining("a", True, now=1.2)     # rotation begins
        assert h.state == DRAINING
        r.set_draining("a", False, now=1.3)    # rotation complete
        assert h.state == DEGRADED             # NOT healthy
        assert h.probation_left == 2           # debt intact
        r.record_success(h, now=1.4)
        r.record_success(h, now=1.5)
        assert h.state == HEALTHY and h.ejections == 0

    def test_degraded_only_when_healthy_unavailable(self):
        r = Router(_policy(degraded_penalty=1e6))
        a = r.add("a", _FakeSession(load=100.0))
        b = r.add("b", _FakeSession(load=0.0))
        r.record_error(b, ServeError("x"), now=0.0)
        r.record_error(b, ServeError("x"), now=0.0)
        assert b.state == EJECTED  # rate 1.0 >= eject
        h = r.place()
        assert h is a
        r.done_placing(h)

    def test_error_rate_degrades_then_ejects_with_backoff(self):
        r = Router(_policy(window=8, min_outcomes=4))
        h = r.add("a", _FakeSession())
        for _ in range(6):
            r.record_success(h, now=0.0)
        r.record_error(h, ServeError("x"), now=0.0)
        r.record_error(h, ServeError("x"), now=0.0)
        assert h.state == DEGRADED          # 2/8 = 0.25 >= degrade
        for _ in range(3):
            r.record_error(h, ServeError("x"), now=0.0)
        assert h.state == EJECTED           # window rate >= 0.5
        assert h.reopen_at == pytest.approx(1.0)  # initial backoff

    def test_circuit_reopens_into_probation_then_healthy(self):
        r = Router(_policy())
        h = r.add("a", _FakeSession())
        r.record_error(h, ServeError("x"), now=0.0)
        r.record_error(h, ServeError("x"), now=0.0)
        assert h.state == EJECTED and h.ejections == 1
        h.session.heartbeat = 0.5
        r.tick(now=0.5)
        assert h.state == EJECTED           # circuit still open
        h.session.heartbeat = 1.1
        r.tick(now=1.1)
        assert h.state == DEGRADED and h.probation_left == 2
        r.record_success(h, now=1.2)
        assert h.state == DEGRADED
        r.record_success(h, now=1.3)
        assert h.state == HEALTHY
        assert h.ejections == 0             # clean bill resets backoff

    def test_error_during_probation_reejects_with_doubled_backoff(self):
        r = Router(_policy())
        h = r.add("a", _FakeSession())
        r.record_error(h, ServeError("x"), now=0.0)
        r.record_error(h, ServeError("x"), now=0.0)
        h.session.heartbeat = 1.1
        r.tick(now=1.1)
        assert h.state == DEGRADED
        r.record_error(h, ServeError("x"), now=1.2)
        assert h.state == EJECTED and h.ejections == 2
        assert h.reopen_at == pytest.approx(1.2 + 2.0)  # doubled
        # backoff is capped
        for k in range(3, 9):
            h.session.heartbeat = h.reopen_at
            r.tick(now=h.reopen_at)
            r.record_error(h, ServeError("x"), now=h.reopen_at)
        assert h.reopen_at - h.last_error_at <= 8.0 + 1e-9

    def test_stale_heartbeat_degrades_then_ejects(self):
        r = Router(_policy(heartbeat_timeout_s=1.0))
        h = r.add("a", _FakeSession())
        h.session.heartbeat = 0.0
        r.tick(now=1.5)
        assert h.state == DEGRADED
        r.tick(now=3.5)                      # stale > 3x timeout
        assert h.state == EJECTED
        # stall clears -> circuit reopens -> probation -> healthy
        reopen = h.reopen_at
        h.session.heartbeat = reopen
        r.tick(now=reopen)
        assert h.state == DEGRADED
        r.record_success(h, now=reopen)
        r.record_success(h, now=reopen)
        assert h.state == HEALTHY

    def test_heartbeat_recovery_without_probation(self):
        """A degrade (not eject) recovers on tick once the condition
        clears — no probation owed."""
        r = Router(_policy(heartbeat_timeout_s=1.0))
        h = r.add("a", _FakeSession())
        h.session.heartbeat = 0.0
        r.tick(now=1.5)
        assert h.state == DEGRADED
        h.session.heartbeat = 2.0
        r.tick(now=2.1)
        assert h.state == HEALTHY

    def test_latency_straggler_degrades(self):
        r = Router(_policy(latency_degrade_ratio=3.0))
        a = r.add("a", _FakeSession())
        b = r.add("b", _FakeSession())
        for _ in range(4):
            r.record_success(a, latency_ms=10.0, now=0.0)
            r.record_success(b, latency_ms=100.0, now=0.0)
        a.session.heartbeat = b.session.heartbeat = 0.1
        r.tick(now=0.1)
        assert a.state == HEALTHY
        assert b.state == DEGRADED
        assert "latency" in b.state_reason

    def test_probation_gets_probe_placements_and_recovers(self):
        """The circuit-breaker half-open trickle: with a healthy
        sibling always preferred, a probationer would starve without
        the every-probe_every-th probe placement — and could never
        serve the successes probation demands."""
        r = Router(_policy(probe_every=4))
        a = r.add("a", _FakeSession(load=0.0))
        b = r.add("b", _FakeSession(load=0.0))
        r.record_error(b, ServeError("x"), now=0.0)
        r.record_error(b, ServeError("x"), now=0.0)
        assert b.state == EJECTED
        b.session.heartbeat = 1.1
        r.tick(now=1.1)
        assert b.state == DEGRADED and b.probation_left == 2
        placed = []
        for _ in range(12):
            h = r.place()
            placed.append(h.rid)
            r.record_success(h, now=1.2)
            r.done_placing(h)
        assert placed.count("b") >= 2, placed
        assert b.state == HEALTHY

    def test_dead_session_is_ejected_permanently(self):
        r = Router(_policy())
        h = r.add("a", _FakeSession())
        h.session.alive = False
        r.tick(now=0.0)
        assert h.state == EJECTED and h.dead
        assert h.reopen_at is None
        r.tick(now=1e9)                      # never re-admits
        assert h.state == EJECTED

    def test_state_changes_report_through_callback(self):
        events = []
        r = Router(_policy(), on_state_change=lambda h, o, n, why:
                   events.append((h.rid, o, n)))
        h = r.add("a", _FakeSession())
        r.record_error(h, ServeError("x"), now=0.0)
        r.record_error(h, ServeError("x"), now=0.0)
        assert events == [("a", HEALTHY, EJECTED)]


# -- the fault injector -----------------------------------------------------


class TestFaultInjector:
    def test_crash_fires_once(self):
        inj = FaultInjector()
        inj.arm(0, "crash")
        with pytest.raises(ReplicaCrash):
            inj.on_dispatch(0)
        assert inj.on_dispatch(0) is None    # dead is dead: one shot
        assert inj.fired("crash") == 1

    def test_faults_are_per_replica(self):
        inj = FaultInjector()
        inj.arm(1, "nan")
        assert inj.on_dispatch(0) is None
        assert inj.on_dispatch(1) == "nan"
        assert inj.on_dispatch(1) is None    # times=1 default

    def test_stall_sleeps(self):
        inj = FaultInjector()
        inj.arm(0, "stall", seconds=0.05)
        t0 = time.perf_counter()
        inj.on_dispatch(0)
        assert time.perf_counter() - t0 >= 0.04

    def test_saturate_sheds_until_cleared(self):
        inj = FaultInjector()
        inj.arm(0, "saturate", times=None)
        with pytest.raises(ServeOverloaded):
            inj.on_admission(0)
        with pytest.raises(ServeOverloaded):
            inj.on_admission(0)
        inj.clear(0, "saturate")
        inj.on_admission(0)                  # no raise

    def test_arm_validates(self):
        inj = FaultInjector()
        with pytest.raises(ValueError, match="kind"):
            inj.arm(0, "gremlins")
        with pytest.raises(ValueError, match="seconds"):
            inj.arm(0, "stall")


# -- one-shot fleet integration ---------------------------------------------


_DIM = 8


def _mlp_fleet(replicas=2, faults=None, anomaly=None, flight=None,
               w_scale=1.0, fleet_kw=None, serve_kw=None):
    """A tiny-MLP one-shot fleet on ONE shared mesh (the in-process
    multi-mesh caution from PR 3 applies; the chaos guard's subprocess
    exercises per-replica submeshes)."""
    params = {"w": np.eye(_DIM, dtype=np.float32) * w_scale}

    def infer_fn(p, b):
        return {"y": (b["x"] @ p["w"]).mean(axis=(1, 2))}

    cfg = parallax.Config(serve_config=ServeConfig(
        max_batch=2, max_wait_ms=1.0, **(serve_kw or {})))
    mesh = mesh_lib.build_mesh()

    def make_replica(rid, **kw):
        return ServeSession(
            infer_fn, params,
            example_feed={"x": np.zeros((4, _DIM), np.float32)},
            config=cfg, mesh=mesh, **kw)

    fc = FleetConfig(num_replicas=replicas, **(fleet_kw or {}))
    return ServeFleet(make_replica, config=fc, faults=faults,
                      anomaly=anomaly, flight=flight), params


def _feed(v):
    return {"x": np.full((4, _DIM), float(v), np.float32)}


class TestFleetOneShot:
    def test_serves_correctly_across_replicas(self):
        fleet, _ = _mlp_fleet()
        try:
            reqs = [fleet.submit(_feed(i)) for i in range(10)]
            for i, r in enumerate(reqs):
                np.testing.assert_allclose(
                    r.result(timeout=30.0)["y"], i, rtol=1e-5)
            s = fleet.stats()
            assert s["fleet.completed"] == 10
            assert s["fleet.replicas_healthy"] == 2
            assert fleet.recompiles() == 0
        finally:
            fleet.close()

    def test_crash_fails_over_without_losing_requests(self):
        inj = FaultInjector()
        fleet, _ = _mlp_fleet(faults=inj)
        try:
            inj.arm(0, "crash")
            reqs = [fleet.submit(_feed(i)) for i in range(8)]
            for i, r in enumerate(reqs):
                np.testing.assert_allclose(
                    r.result(timeout=30.0)["y"], i, rtol=1e-5)
            s = fleet.stats()
            assert s["replicas"]["0"]["state"] == EJECTED
            assert s["replicas"]["0"]["dead"] is True
            assert s["fleet.ejections"] >= 1
            # at least the batch in flight when the crash fired (plus
            # anything queued behind it) failed over
            assert s["fleet.failovers"] >= 1
            assert s["fleet.failed"] == 0
        finally:
            fleet.close()

    def test_failover_trail_recorded_on_the_request(self):
        inj = FaultInjector()
        fleet, _ = _mlp_fleet(faults=inj)
        try:
            inj.arm(0, "crash")
            reqs = [fleet.submit(_feed(i)) for i in range(8)]
            for i, r in enumerate(reqs):
                np.testing.assert_allclose(
                    r.result(timeout=30.0)["y"], i, rtol=1e-5)
            # the crash fired on replica 0's first dispatched batch,
            # so the requests it held show the two-hop trail
            trails = [r.replicas for r in reqs]
            assert any(t == [0, 1] for t in trails), trails
        finally:
            fleet.close()

    def test_whole_fleet_death_fails_fast_and_retryably(self):
        inj = FaultInjector()
        fleet, _ = _mlp_fleet(faults=inj)
        try:
            inj.arm(0, "crash")
            inj.arm(1, "crash")
            reqs = [fleet.submit(_feed(i)) for i in range(4)]
            for r in reqs:
                # never hangs, never delivers garbage: each request
                # fails promptly with the RETRYABLE error once no
                # replica remains (a client tier may resubmit later)
                with pytest.raises(ReplicaUnavailable):
                    r.result(timeout=30.0)
        finally:
            fleet.close()

    def test_nan_output_is_detected_and_retried(self):
        """check_outputs (fleet default): a NaN batch fails RETRYABLY
        instead of reaching a client, and the retry serves real
        numbers from a healthy replica."""
        inj = FaultInjector()
        fleet, _ = _mlp_fleet(faults=inj)
        try:
            inj.arm(0, "nan", times=1)
            inj.arm(1, "nan", times=1)
            reqs = [fleet.submit(_feed(i)) for i in range(8)]
            for i, r in enumerate(reqs):
                out = r.result(timeout=30.0)
                assert np.isfinite(out["y"]).all()
                np.testing.assert_allclose(out["y"], i, rtol=1e-5)
            assert fleet.stats()["fleet.retries"] >= 1
        finally:
            fleet.close()

    def test_saturation_spills_then_sheds_fleet_wide(self):
        inj = FaultInjector()
        fleet, _ = _mlp_fleet(faults=inj)
        try:
            inj.arm(0, "saturate", times=None)
            # one replica saturated: traffic spills to the other
            reqs = [fleet.submit(_feed(i)) for i in range(4)]
            for i, r in enumerate(reqs):
                np.testing.assert_allclose(
                    r.result(timeout=30.0)["y"], i, rtol=1e-5)
            assert all(r.replicas == [1] for r in reqs)
            # every replica saturated: the fleet sheds synchronously
            inj.arm(1, "saturate", times=None)
            with pytest.raises(ServeOverloaded):
                fleet.submit(_feed(0))
            assert fleet.stats()["fleet.shed"] == 1
        finally:
            fleet.close()

    def test_hot_swap_takes_effect_with_zero_recompiles(self):
        fleet, params = _mlp_fleet()
        try:
            r = fleet.submit(_feed(3))
            np.testing.assert_allclose(r.result(timeout=30.0)["y"],
                                       3.0, rtol=1e-5)
            outcome = fleet.push_weights(
                {"w": np.eye(_DIM, dtype=np.float32) * 2.0})
            assert set(outcome.values()) == {"swapped"}
            r = fleet.submit(_feed(3))
            np.testing.assert_allclose(r.result(timeout=30.0)["y"],
                                       6.0, rtol=1e-5)
            s = fleet.stats()
            assert s["fleet.hotswaps"] == 2
            assert s["fleet.drain_seconds"]["count"] == 2
            assert s["fleet.replicas_healthy"] == 2
            assert fleet.recompiles() == 0
        finally:
            fleet.close()

    def test_scale_up_after_push_serves_pushed_weights(self):
        """Stale weights must not rejoin — including via scale-up: a
        replica added AFTER push_weights comes up on the pushed
        checkpoint, not on whatever the factory closure captured."""
        fleet, _ = _mlp_fleet(fleet_kw={"max_replicas": 3})
        try:
            fleet.push_weights(
                {"w": np.eye(_DIM, dtype=np.float32) * 2.0})
            rid = fleet.scale_up()
            assert rid is not None
            # route to the newcomer specifically
            h = fleet._router.get(rid)
            sub = h.session.submit(_feed(3))
            np.testing.assert_allclose(sub.result(timeout=30.0)["y"],
                                       6.0, rtol=1e-5)
            assert fleet.recompiles() == 0
        finally:
            fleet.close()

    def test_one_bad_batch_does_not_eject_a_replica(self):
        """Error accounting is per REQUEST, symmetric with success
        accounting — a single transient bad batch on a warm replica
        must not blow through the ejection threshold."""
        inj = FaultInjector()
        fleet, _ = _mlp_fleet(faults=inj)
        try:
            # warm both replicas' outcome windows with successes
            for i in range(12):
                fleet.submit(_feed(i)).result(timeout=30.0)
            inj.arm(0, "nan", times=1)
            inj.arm(1, "nan", times=1)
            reqs = [fleet.submit(_feed(i)) for i in range(4)]
            for i, r in enumerate(reqs):
                np.testing.assert_allclose(
                    r.result(timeout=30.0)["y"], i, rtol=1e-5)
            s = fleet.stats()
            # a DEGRADE is fine (each replica did take a bad batch);
            # an EJECTION — halving capacity over one transient — is
            # the double-counting bug this test pins down
            assert s["fleet.ejections"] == 0, s["replicas"]
            assert all(v["state"] in (HEALTHY, DEGRADED)
                       for v in s["replicas"].values()), s["replicas"]
        finally:
            fleet.close()

    def test_swap_refuses_architecture_change(self):
        fleet, _ = _mlp_fleet()
        try:
            with pytest.raises(RuntimeError, match="hot-swap failed"):
                fleet.push_weights(
                    {"w": np.zeros((_DIM, _DIM + 1), np.float32)})
            # the refusing replicas are ejected (stale weights must
            # not rejoin silently) and the failure is counted
            s = fleet.stats()
            assert s["fleet.hotswap_failures"] == 2
            assert all(v["state"] == EJECTED
                       for v in s["replicas"].values())
        finally:
            fleet.close()

    def test_deadline_respected_across_failover(self):
        """A retry never extends the budget: with every replica dead,
        the request fails promptly (retryably) instead of spinning."""
        inj = FaultInjector()
        fleet, _ = _mlp_fleet(faults=inj)
        try:
            inj.arm(0, "crash")
            inj.arm(1, "crash")
            admitted = 0
            for i in range(4):
                try:
                    r = fleet.submit(_feed(i), deadline_ms=5000.0)
                except ReplicaUnavailable:
                    # the whole fleet died before this submit — a
                    # synchronous refusal at admission is also correct
                    continue
                admitted += 1
                with pytest.raises((ReplicaUnavailable,
                                    DeadlineExceeded)):
                    r.result(timeout=30.0)
            assert admitted >= 1  # the first submit always lands
        finally:
            fleet.close()

    def test_submit_after_close_raises(self):
        fleet, _ = _mlp_fleet()
        fleet.close()
        with pytest.raises(ServeClosed):
            fleet.submit(_feed(0))


# -- autoscaler (fake replicas, deterministic) ------------------------------


class TestAutoscaler:
    def _fleet(self, **fc_kw):
        sessions = []

        def make_replica(rid, **kw):
            s = _FakeSession(load=0.0)
            s.heartbeat = time.perf_counter()
            sessions.append(s)
            return s

        fc = FleetConfig(num_replicas=1, min_replicas=1,
                         max_replicas=3, autoscale=True,
                         autoscale_high_load=4.0,
                         autoscale_low_load=0.5,
                         autoscale_sustain_ticks=2,
                         tick_interval_s=3600.0,  # test drives ticks
                         **fc_kw)
        return ServeFleet(make_replica, config=fc), sessions

    @staticmethod
    def _settle(fleet, n, timeout=5.0):
        """Scale actions run OFF the maintenance thread (a drain or a
        cold compile must not freeze the health probes) — wait for the
        spawned action to land."""
        end = time.perf_counter() + timeout
        while time.perf_counter() < end:
            if fleet.num_replicas == n and not fleet._autoscale_busy:
                return
            time.sleep(0.005)
        raise AssertionError(
            f"fleet did not settle at {n} replicas "
            f"(at {fleet.num_replicas})")

    def test_scales_up_on_sustained_pressure_only(self):
        fleet, sessions = self._fleet()
        try:
            sessions[0]._load = 10.0
            fleet._autoscale_tick()          # 1 tick: not sustained
            assert fleet.num_replicas == 1
            fleet._autoscale_tick()          # sustained -> scale up
            self._settle(fleet, 2)
            assert fleet.stats()["fleet.scale_ups"] == 1
            # a blip does not scale: counter resets between
            sessions[0]._load = 1.0
            sessions[1]._load = 1.0
            fleet._autoscale_tick()
            sessions[0]._load = 10.0
            sessions[1]._load = 10.0
            fleet._autoscale_tick()
            self._settle(fleet, 2)

        finally:
            fleet.close()

    def test_scales_down_via_graceful_drain_never_below_min(self):
        fleet, sessions = self._fleet()
        try:
            sessions[0]._load = 10.0
            fleet._autoscale_tick()
            fleet._autoscale_tick()
            self._settle(fleet, 2)
            sessions[0]._load = 0.0
            fleet._autoscale_tick()
            fleet._autoscale_tick()
            self._settle(fleet, 1)
            assert any(s.closed for s in sessions)  # drained close
            fleet._autoscale_tick()
            fleet._autoscale_tick()
            self._settle(fleet, 1)           # min_replicas floor
        finally:
            fleet.close()

    def test_scale_up_bounded_by_max_replicas(self):
        fleet, sessions = self._fleet()
        try:
            assert fleet.scale_up() is not None
            assert fleet.scale_up() is not None
            assert fleet.scale_up() is None  # at max_replicas=3
            assert fleet.num_replicas == 3
        finally:
            fleet.close()


# -- deliberate changes must not read as anomalies --------------------------


class TestAnomalyRebaseline:
    def _monitor(self):
        from parallax_tpu.common.config import AnomalyConfig
        from parallax_tpu.obs.anomaly import AnomalyMonitor
        return AnomalyMonitor(config=AnomalyConfig(
            window=32, min_samples=8, shift_window=4,
            shift_ratio=1.5, cooldown=16))

    def test_level_change_fires_shift_without_notice(self):
        # 10 -> 16: a sustained +60% level move — below the 2x spike
        # ratio, above the 1.5x shift ratio (the change-point case)
        mon = self._monitor()
        events = [e for i in range(20)
                  if (e := mon.observe("step_time_ms", i, 10.0))]
        assert not events
        fired = [mon.observe("step_time_ms", 20 + i, 16.0)
                 for i in range(8)]
        assert any(e is not None and e.kind == "shift" for e in fired)

    def test_notified_scale_event_does_not_fire(self):
        mon = self._monitor()
        for i in range(20):
            assert mon.observe("step_time_ms", i, 10.0) is None
        # the fleet announces the deliberate change (scale-up,
        # ejection failover, hot-swap) -> rebaseline, no change-point
        mon.notify_deliberate_change("fleet scale-up")
        for i in range(30):
            assert mon.observe("step_time_ms", 20 + i, 16.0) is None
        snap = mon.registry.snapshot()
        assert snap["anomaly.deliberate_changes"] == 1
        assert "anomaly.step_time_ms.shifts" not in snap

    def test_fleet_scale_event_reaches_the_monitor(self):
        mon = self._monitor()
        sessions = []

        def make_replica(rid, **kw):
            s = _FakeSession()
            s.heartbeat = time.perf_counter()
            sessions.append(s)
            return s

        fleet = ServeFleet(make_replica,
                           config=FleetConfig(num_replicas=1,
                                              max_replicas=2,
                                              tick_interval_s=3600.0),
                           anomaly=mon)
        try:
            fleet.scale_up()
            assert mon.registry.snapshot()[
                "anomaly.deliberate_changes"] >= 1
        finally:
            fleet.close()


# -- decode failover token identity (paged KV, in-process) ------------------


def test_decode_failover_token_identity_paged():
    """ISSUE 7 satellite: a request retried onto a second replica
    after an injected crash emits the SAME greedy tokens as an
    unfaulted standalone decode — under a paged-KV program, where the
    dead replica's pages are simply abandoned with it and the retry
    allocates fresh ones on the survivor. Shared mesh (in-process
    multi-mesh caution); the subprocess chaos guard covers the
    per-replica-submesh shape.

    ISSUE 12 satellite, same rig: cross-thread ``trace.record_span``
    under failover — each logical request surfaces EXACTLY ONE
    ``serve.request`` span (the dead hop never retires, so only the
    delivering replica emits), carrying the final replica id and the
    hop count."""
    from parallax_tpu.models import nmt
    from parallax_tpu.obs import trace
    from tools import loadgen

    inj = FaultInjector()
    fleet, make_feed, params, cfg = loadgen.demo_decode_fleet(
        replicas=2, slots=2, T=8, Ts=6, model_dim=16, vocab=64,
        page_size=4, faults=inj, submesh=False)
    n = 8
    col = trace.TraceCollector(capacity=4096)
    prev = trace.set_collector(col)
    try:
        reqs = [fleet.submit(make_feed(i)) for i in range(n)]
        while sum(1 for r in reqs if r.done()) < 1:
            time.sleep(0.005)
        victim = max((h for h in fleet._router.handles()
                      if h.session.alive),
                     key=lambda h: h.session.load())
        inj.arm(victim.rid, "crash")
        outs = [r.result(timeout=120.0) for r in reqs]
        retried = [r for r in reqs if len(r.replicas) > 1]
        assert retried, "the crash caused no failover"
        assert fleet.recompiles() == 0
    finally:
        fleet.close()
        trace.set_collector(prev)
    for i, (r, out) in enumerate(zip(reqs, outs)):
        src = make_feed(i)["src"]
        ref = np.asarray(nmt.greedy_decode(
            params, cfg, src[None], max_len=8))[0].tolist()
        if nmt.EOS_ID in ref:
            ref = ref[:ref.index(nmt.EOS_ID) + 1]
        assert list(out) == ref, (i, r.replicas, list(out), ref)
    # the trace contract: one span per logical request, final replica
    # id + hop count in-args (keyed by the fleet request id the shared
    # lifecycle record carries across hops)
    spans = {}
    for ev in col.events():
        if ev.name == "serve.request":
            spans.setdefault(ev.args["rid"], []).append(ev)
    for r in reqs:
        assert len(spans.get(r.id, [])) == 1, \
            (r.id, r.replicas, spans.get(r.id))
        args = spans[r.id][0].args
        assert args["replica"] == r.replicas[-1], (args, r.replicas)
        assert args["hops"] == len(r.replicas), (args, r.replicas)
    survivor_hops = {len(r.replicas) for r in retried}
    assert survivor_hops == {2}


def test_incident_dump_correlates_fleet_state(tmp_path):
    """ISSUE 12: a replica crash produces ONE correlated artifact —
    shared incident id, the crashed replica named, every affected
    request id with its failover hop trail, router health +
    circuit-breaker states, the in-flight request table and the
    per-replica registries, all in the same JSON."""
    import glob
    import json as json_mod

    from parallax_tpu.obs.flightrec import FlightRecorder

    inj = FaultInjector()
    flight = FlightRecorder(flight_dir=str(tmp_path))
    fleet, _ = _mlp_fleet(faults=inj, flight=flight)
    try:
        inj.arm(0, "crash")
        reqs = [fleet.submit(_feed(i)) for i in range(8)]
        for r in reqs:
            r.result(timeout=30.0)
        retried = [r for r in reqs if len(r.replicas) > 1]
        assert retried
    finally:
        fleet.close()
    dumps = glob.glob(str(tmp_path / "flight_fleet_crash*.json"))
    assert len(dumps) == 1
    doc = json_mod.load(open(dumps[0]))
    assert doc["incident_id"]
    assert doc["detail"]["replica"] == 0
    affected = {a["id"]: a["hops"]
                for a in doc["detail"]["affected_requests"]}
    for r in retried:
        assert affected.get(r.id) == r.replicas, (r.id, affected)
    # correlated sections: router health + circuit state, the
    # in-flight table, fleet aggregates with per-replica serve.*
    states = {row["rid"]: row for row in doc["router"]}
    assert states[0]["state"] == EJECTED and states[0]["dead"]
    assert "circuit" in states[0] and "heartbeat_age_s" in states[0]
    assert isinstance(doc["requests_in_flight"], list)
    assert doc["fleet"]["replicas"]["0"]["serve"]
    # the fleet request records ride along for post-hoc attribution
    assert isinstance(doc["request_records"], list)


# -- the tier-1 chaos guard (subprocess driver) -----------------------------


def test_fleet_chaos_guard():
    """tools/check_fleet_faults.py: with 2 replicas under closed-loop
    load, an injected replica crash and a mid-traffic weight hot-swap
    complete with zero dropped accepted requests, zero late service,
    zero serve-time recompiles on every replica (fresh and swapped),
    bit-identical greedy tokens on failover-retried requests, and a
    flight-recorder artifact naming the fleet_crash incident. Run as a
    subprocess (its own __main__ contract) for the same toolchain-
    crash isolation as the SLO and compile-budget guards."""
    tool = os.path.join(os.path.dirname(__file__), "..", "tools",
                        "check_fleet_faults.py")
    result = _run_driver_json([sys.executable, tool],
                              check_rc=False, timeout=600.0)
    assert result["ok"], result.get("violations", result)
    assert result["crash"]["retried_requests"] >= 1
    assert result["hotswap"]["hotswaps"] == 2
    assert result["bench"]["recompiles"] == 0
