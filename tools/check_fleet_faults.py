"""Fleet chaos guard: crash failover + mid-traffic hot-swap, gated.

ISSUE 7 acceptance, enforced in tier-1
(tests/test_fleet.py::test_fleet_chaos_guard via the established
subprocess-driver pattern) and runnable directly::

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python tools/check_fleet_faults.py

Three phases over a 2-replica tiny-NMT continuous-decode fleet
(tools/loadgen.py ``demo_decode_fleet`` — each replica a full
ServeSession with paged KV on its own submesh):

* **baseline** — per-request greedy tokens computed OUTSIDE serving
  (``nmt.greedy_decode``), the bit-identity reference for everything
  below. Greedy decode is deterministic, so any healthy replica — and
  any failover retry — must reproduce it exactly.
* **crash** — the full request set is accepted, then one loaded
  replica is killed mid-flight (serve/faults.py injected crash). The
  contract: ZERO dropped accepted requests (the dead replica's
  accepted-but-unserved work fails over within the original
  deadline), zero late service, zero serve-time recompiles on the
  survivor (``serve.recompiles`` AND a ``jax.monitoring``
  backend-compile witness), every request — retried or not — emitting
  bit-identical tokens to the baseline, and a flight-recorder
  artifact naming the ``fleet_crash`` incident. The paged-KV pages
  held on the dead replica are simply abandoned with it; the retry
  allocates fresh pages on the survivor. ``failover_recovery_ms`` =
  crash injection -> last failed-over request completed.
* **hotswap** — a fresh 2-replica fleet under continuing closed-loop
  load gets ``push_weights`` mid-traffic. The pushed checkpoint is a
  value-identical COPY of the serving params (host round-trip), so
  the rotation machinery — drain, ``swap_params`` on the same mesh,
  re-admission — is fully exercised while the token-identity bar
  stays assertable; a separate unit test
  (tests/test_fleet.py) proves a *different* checkpoint actually
  changes outputs. The contract: zero dropped, zero late, 2 swaps,
  zero recompiles on fresh AND swapped replicas (a post-swap request
  wave re-checks), tokens identical. ``hotswap_blackout_ms`` = the
  longest fleet-wide gap between request completions inside the swap
  window — with >= 2 replicas the fleet must keep completing work
  while each one rotates.

The XLA-compile witness is paused around ``push_weights`` itself (a
``device_put`` of fresh arrays may legitimately build a transfer
program; the zero-recompile claim is about SERVING dispatches, which
``serve.recompiles`` covers end to end and the witness re-arms for).

The ``bench`` sub-dict is the summary tests/test_fleet.py reads. All
numbers are CPU runs: not measured on the chip.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

_compile_events = {"n": 0, "active": False}


def _install_listener():
    import jax

    def _listen(event, duration, **kw):
        if _compile_events["active"] and "backend_compile" in event:
            _compile_events["n"] += 1

    jax.monitoring.register_event_duration_secs_listener(_listen)


def _baseline_tokens(params, cfg, make_feed, n: int, max_len: int):
    """Reference greedy tokens per request, computed outside serving."""
    import numpy as np

    from parallax_tpu.models import nmt

    out = []
    for i in range(n):
        src = make_feed(i)["src"]
        ref = np.asarray(nmt.greedy_decode(
            params, cfg, src[None], max_len=max_len))[0].tolist()
        if nmt.EOS_ID in ref:
            ref = ref[:ref.index(nmt.EOS_ID) + 1]
        out.append(ref)
    return out


def _await_all(reqs, timeout_s=300.0):
    """Collect every future's outcome; returns (dropped, late,
    completions) where completions maps index -> (tokens, t_done,
    replicas)."""
    dropped, late, done = [], [], {}
    for i, r in enumerate(reqs):
        try:
            toks = r.result(timeout=timeout_s)
        except Exception as e:
            dropped.append((i, f"{type(e).__name__}: {e}"))
            continue
        if r.deadline is not None and r.t_done > r.deadline:
            late.append(i)
        done[i] = (list(toks), r.t_done, list(r.replicas))
    return dropped, late, done


def _mismatches(done, baseline):
    bad = []
    for i, (toks, _t, _reps) in done.items():
        if toks != baseline[i]:
            bad.append({"request": i, "got": toks,
                        "want": baseline[i]})
    return bad


def measure(n_requests: int = 20, slots: int = 4, T: int = 12,
            Ts: int = 6, deadline_ms: float = 120000.0,
            model_dim: int = 32, vocab: int = 64) -> dict:
    import numpy as np

    from parallax_tpu.obs.flightrec import FlightRecorder
    from parallax_tpu.serve import FaultInjector
    from tools import loadgen

    _install_listener()
    flight_dir = tempfile.mkdtemp(prefix="fleet_flight_")
    result: dict = {"flight_dir": flight_dir}

    # -- phase 1+2: crash failover under load --------------------------
    inj = FaultInjector()
    flight = FlightRecorder(flight_dir=flight_dir)
    fleet, make_feed, params, cfg = loadgen.demo_decode_fleet(
        replicas=2, slots=slots, T=T, Ts=Ts, model_dim=model_dim,
        vocab=vocab, faults=inj, flight=flight)
    baseline = _baseline_tokens(params, cfg, make_feed, n_requests, T)
    try:
        _compile_events["n"] = 0
        _compile_events["active"] = True
        reqs = [fleet.submit(make_feed(i), deadline_ms=deadline_ms)
                for i in range(n_requests)]
        # let the fleet get properly in flight, then kill the replica
        # carrying the most work
        while sum(1 for r in reqs if r.done()) < max(2, n_requests // 8):
            time.sleep(0.005)
        router = fleet._router
        victim = max((h for h in router.handles() if h.session.alive),
                     key=lambda h: h.session.load())
        t_crash = time.perf_counter()
        inj.arm(victim.rid, "crash")
        dropped, late, done = _await_all(reqs)
        _compile_events["active"] = False
        retried = {i: v for i, v in done.items() if len(v[2]) > 1}
        mism1 = _mismatches(done, baseline)
        recovery_ms = (max((v[1] for v in retried.values()),
                           default=t_crash) - t_crash) * 1e3
        stats = fleet.stats()
        result["crash"] = {
            "requests": n_requests,
            "victim_replica": victim.rid,
            "dropped": len(dropped),
            "dropped_detail": dropped[:5],
            "late": len(late),
            "completed": len(done),
            "retried_requests": len(retried),
            "failovers": stats.get("fleet.failovers", 0),
            "ejections": stats.get("fleet.ejections", 0),
            "token_mismatch_count": len(mism1),
            "token_mismatches": mism1[:5],  # detail only; count above
            "recompiles": fleet.recompiles(),
            "serve_time_xla_compiles": _compile_events["n"],
            "failover_recovery_ms": round(recovery_ms, 3),
            "replica_states": {k: v["state"] for k, v in
                               stats["replicas"].items()},
        }
    finally:
        fleet.close()
    crash_artifacts = [p for p in flight.dump_paths
                      if "fleet_crash" in os.path.basename(p)]
    result["crash"]["flight_artifacts"] = crash_artifacts
    # request forensics (ISSUE 12): every completed request's TTFT
    # decomposition must sum to its measured client-side TTFT (the
    # phase machine partitions the client window by construction, so
    # a drift here means a phase is being dropped or double-counted)
    ttft_errs = []
    for r in reqs:
        rec = getattr(r, "rec", None)
        if rec is None or r.t_first_token is None \
                or rec.ttft_decomp is None:
            continue
        client_ttft_ms = (r.t_first_token - r.t_enqueue) * 1e3
        decomp_sum = sum(rec.ttft_decomp.values())
        if client_ttft_ms > 0:
            ttft_errs.append(abs(decomp_sum - client_ttft_ms)
                             / client_ttft_ms)
    result["crash"]["ttft_decomp_checked"] = len(ttft_errs)
    result["crash"]["ttft_decomp_max_rel_err"] = (
        round(max(ttft_errs), 5) if ttft_errs else None)
    # the correlated incident artifact: ONE dump that names the
    # crashed replica, stamps a shared incident id, captures router
    # health + circuit-breaker states and the in-flight table, and
    # lists every affected request with its failover hop trail
    incident = {}
    if crash_artifacts:
        with open(crash_artifacts[0]) as f:
            doc = json.load(f)
        det = doc.get("detail") or {}
        affected = det.get("affected_requests") or []
        by_id = {a.get("id"): a.get("hops") or [] for a in affected}
        retried_ids = [r.id for r in reqs if len(r.replicas) > 1]
        incident = {
            "incident_id": doc.get("incident_id"),
            "replica_named": det.get("replica"),
            "affected_count": len(affected),
            "affected_sample": affected[:5],
            "has_router_section": isinstance(doc.get("router"), list),
            "has_inflight_table": isinstance(
                doc.get("requests_in_flight"), list),
            "has_fleet_section": isinstance(doc.get("fleet"), dict),
            "retried_ids": retried_ids,
            "retried_ids_covered": all(
                rid_ in by_id
                and victim.rid in by_id[rid_]
                and len(by_id[rid_]) > 1
                for rid_ in retried_ids),
        }
    result["crash"]["incident"] = incident

    # -- phase 3: mid-traffic weight hot-swap --------------------------
    flight2 = FlightRecorder(flight_dir=flight_dir)
    fleet2, make_feed, params, cfg = loadgen.demo_decode_fleet(
        replicas=2, slots=slots, T=T, Ts=Ts, model_dim=model_dim,
        vocab=vocab, flight=flight2)
    # a value-identical checkpoint via host round-trip: exercises the
    # full rotation machinery while keeping tokens assertable
    import jax
    pushed = jax.tree.map(lambda x: np.array(x), params)
    try:
        _compile_events["n"] = 0
        _compile_events["active"] = True
        reqs2 = []
        stop = threading.Event()

        def client(k):
            i = k
            while i < n_requests and not stop.is_set():
                reqs2.append(fleet2.submit(make_feed(i),
                                           deadline_ms=deadline_ms))
                i += 4

        threads = [threading.Thread(target=client, args=(k,),
                                    daemon=True) for k in range(4)]
        for t in threads:
            t.start()
        while sum(1 for r in list(reqs2) if r.done()) < 2:
            time.sleep(0.005)
        _compile_events["active"] = False  # device_put may compile a
        t_swap0 = time.perf_counter()      # transfer program
        outcome = fleet2.push_weights(pushed)
        t_swap1 = time.perf_counter()
        _compile_events["active"] = True
        for t in threads:
            t.join(timeout=300.0)
        # post-swap wave: swapped executables must serve compile-free
        wave = [fleet2.submit(make_feed(i), deadline_ms=deadline_ms)
                for i in range(n_requests)]
        dropped2, late2, done2 = _await_all(list(reqs2) + wave)
        _compile_events["active"] = False
        # blackout: longest completion gap fleet-wide inside the swap
        # window (edges included — an empty window reads as the whole)
        times = sorted(t for _i, (_tk, t, _r) in done2.items()
                       if t_swap0 <= t <= t_swap1)
        marks = [t_swap0] + times + [t_swap1]
        blackout_ms = max(b - a for a, b in zip(marks, marks[1:])) * 1e3
        all_reqs = list(reqs2) + wave
        # reference per request by replaying its OWN (padded) feed —
        # the submit order across client threads is nondeterministic
        mism = _hotswap_mismatches(done2, all_reqs, params, cfg, T)
        stats2 = fleet2.stats()
        result["hotswap"] = {
            "requests": len(all_reqs),
            "dropped": len(dropped2),
            "dropped_detail": dropped2[:5],
            "late": len(late2),
            "completed": len(done2),
            "outcome": {str(k): v for k, v in outcome.items()},
            "hotswaps": stats2.get("fleet.hotswaps", 0),
            "hotswap_failures": stats2.get("fleet.hotswap_failures", 0),
            "drain_seconds": stats2.get("fleet.drain_seconds"),
            "token_mismatch_count": len(mism),
            "token_mismatches": mism[:5],  # detail only; count above
            "recompiles": fleet2.recompiles(),
            "serve_time_xla_compiles": _compile_events["n"],
            "hotswap_blackout_ms": round(blackout_ms, 3),
            "swap_window_ms": round((t_swap1 - t_swap0) * 1e3, 3),
        }
    finally:
        fleet2.close()

    c, h = result["crash"], result["hotswap"]
    result["bench"] = {
        "replicas": 2,
        "failover_recovery_ms": c["failover_recovery_ms"],
        "hotswap_blackout_ms": h["hotswap_blackout_ms"],
        "failovers": c["failovers"],
        "hotswaps": h["hotswaps"],
        "dropped": c["dropped"] + h["dropped"],
        "late": c["late"] + h["late"],
        "recompiles": c["recompiles"] + h["recompiles"],
        "token_mismatches": (c["token_mismatch_count"]
                             + h["token_mismatch_count"]),
        "incident_correlated": bool(
            c.get("incident", {}).get("incident_id")
            and c["incident"].get("retried_ids_covered")),
        "ttft_decomp_max_rel_err": c.get("ttft_decomp_max_rel_err"),
    }
    return result


def _hotswap_mismatches(done, reqs, params, cfg, max_len):
    """Reference tokens per completed request by replaying its OWN
    feed through standalone greedy decode (the pushed checkpoint is
    value-identical, so one reference serves pre- and post-swap)."""
    import numpy as np

    from parallax_tpu.models import nmt

    bad = []
    for i, (toks, _t, _reps) in done.items():
        src = np.asarray(reqs[i].feed["src"])
        src = src[src != 0] if src.ndim == 1 else src
        ref = np.asarray(nmt.greedy_decode(
            params, cfg, src[None], max_len=max_len))[0].tolist()
        if nmt.EOS_ID in ref:
            ref = ref[:ref.index(nmt.EOS_ID) + 1]
        if list(toks) != ref:
            bad.append({"request": i, "got": list(toks), "want": ref})
    return bad


def check(result: dict) -> list:
    """-> list of violated invariants (empty = pass)."""
    bad = []
    c = result["crash"]
    if c["dropped"]:
        bad.append(f"crash phase dropped {c['dropped']} accepted "
                   f"request(s): {c['dropped_detail']}")
    if c["late"]:
        bad.append(f"crash phase served {c['late']} request(s) late")
    if c["completed"] != c["requests"]:
        bad.append(f"crash phase completed {c['completed']}/"
                   f"{c['requests']}")
    if c["retried_requests"] == 0:
        bad.append("the injected crash caused no failover — the chaos "
                   "harness did not exercise the contract")
    if c["token_mismatch_count"]:
        bad.append(f"failover broke token identity on "
                   f"{c['token_mismatch_count']} request(s): "
                   f"{c['token_mismatches']}")
    if c["recompiles"] != 0:
        bad.append(f"crash phase serve.recompiles = {c['recompiles']}")
    if c["serve_time_xla_compiles"] != 0:
        bad.append(f"{c['serve_time_xla_compiles']} XLA compile(s) "
                   f"during crash-phase serving")
    if not c["flight_artifacts"]:
        bad.append("no flight-recorder artifact names the fleet_crash "
                   "incident")
    inc = c.get("incident") or {}
    if c["flight_artifacts"]:
        if not inc.get("incident_id"):
            bad.append("fleet_crash artifact carries no incident_id")
        if inc.get("replica_named") != c["victim_replica"]:
            bad.append(
                f"fleet_crash artifact names replica "
                f"{inc.get('replica_named')!r}, not the crashed "
                f"{c['victim_replica']!r}")
        if not inc.get("retried_ids_covered"):
            bad.append(
                f"fleet_crash artifact's affected_requests does not "
                f"cover every failed-over request with its hop trail "
                f"(retried={inc.get('retried_ids')}, "
                f"affected={inc.get('affected_sample')})")
        for section in ("has_router_section", "has_inflight_table",
                        "has_fleet_section"):
            if not inc.get(section):
                bad.append(f"fleet_crash artifact missing correlated "
                           f"section: {section[4:]}")
    if not c.get("ttft_decomp_checked"):
        bad.append("no per-request TTFT decompositions were available "
                   "to verify")
    elif c["ttft_decomp_max_rel_err"] > 0.05:
        bad.append(
            f"per-request TTFT decomposition drifts "
            f"{c['ttft_decomp_max_rel_err'] * 100:.2f}% from the "
            f"measured client-side TTFT (> 5%)")
    h = result["hotswap"]
    if h["dropped"]:
        bad.append(f"hot-swap phase dropped {h['dropped']} accepted "
                   f"request(s): {h['dropped_detail']}")
    if h["late"]:
        bad.append(f"hot-swap phase served {h['late']} request(s) late")
    if h["hotswaps"] != 2 or h["hotswap_failures"]:
        bad.append(f"expected 2 clean hot-swaps, got "
                   f"{h['hotswaps']} ({h['hotswap_failures']} failed)")
    if h["token_mismatch_count"]:
        bad.append(f"hot-swap broke token identity on "
                   f"{h['token_mismatch_count']} request(s): "
                   f"{h['token_mismatches']}")
    if h["recompiles"] != 0:
        bad.append(f"hot-swap phase serve.recompiles = "
                   f"{h['recompiles']} — the swap invalidated the AOT "
                   f"executable set")
    if h["serve_time_xla_compiles"] != 0:
        bad.append(f"{h['serve_time_xla_compiles']} XLA compile(s) "
                   f"during hot-swap-phase serving")
    return bad


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--requests", type=int, default=20)
    ap.add_argument("--slots", type=int, default=4)
    args = ap.parse_args(argv)
    result = measure(n_requests=args.requests, slots=args.slots)
    violations = check(result)
    result["violations"] = violations
    result["ok"] = not violations
    print(json.dumps(result, indent=2, default=str))
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
