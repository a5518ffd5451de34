"""Goodput-ledger chaos guard: the run account must survive contact
with failure — gated.

ISSUE 20 acceptance, enforced in tier-1
(tests/test_ops.py::test_goodput_chaos_guard via the established
subprocess-driver pattern) and runnable directly::

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python tools/check_goodput.py

Three phases over the deterministic simple-model loop (same rig as
tools/check_train_faults.py):

* **clean** — N uninterrupted steps. The ledger's account must sum to
  its wall EXACTLY (``unattributed`` is the constructed remainder),
  the ledger wall must agree with the parent-measured wall (child
  spawn epoch -> child end stamp) within 5% (the
  ``PARALLAX_RUN_EPOCH`` anchor working), and the built-in alert
  rules must fire ZERO alerts on a healthy run.
* **sigkill-resume** — checkpoints every k steps, SIGKILL mid-run,
  relaunch. The resumed ledger (restored through the checkpoint
  manifest extras) must span BOTH attempts: ``attempts == 2``,
  ``restore_replay > 0`` (the restore-verify wall), and
  ``eviction_downtime > 0`` (save -> respawn dead air, which includes
  the lost unsaved tail); its cumulative wall must agree with the
  parent's two-spawn measurement within 5%.
* **nan-rollback** — one poisoned batch under auto-recovery: the
  discarded steps' measured time must land in ``rollback_discarded``
  (> 0), and the journal must carry the
  ``recovery/nonfinite_rollback`` and ``ops/rollback_discarded``
  events in causal order.

All numbers are CPU runs: not measured on the chip.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

STEPS = 12
CKPT_EVERY = 4
WALL_TOLERANCE = 0.05  # ledger wall vs parent-measured wall


# ---------------------------------------------------------------------------
# child: one deterministic training run, account written at exit
# ---------------------------------------------------------------------------

def _batch_for(i: int, nan: bool = False):
    import numpy as np
    from parallax_tpu.models import simple
    b = simple.make_batch(np.random.default_rng(1000 + i), 32)
    if nan:
        b["x"] = b["x"] * np.nan
    return b


def child_main(args) -> int:
    import parallax_tpu as parallax
    from parallax_tpu.models import simple

    nan_at = {int(s) for s in args.nan_at.split(",") if s}
    cfg = parallax.Config(
        run_option="AR", search_partitions=False,
        flight_dir=args.flight_dir or None,
        journal_path=args.journal or None,
        ckpt_config=parallax.CheckPointConfig(
            ckpt_dir=args.ckpt_dir or None,
            save_ckpt_steps=CKPT_EVERY if args.ckpt_dir else None),
        recovery_config=parallax.RecoveryConfig(
            enabled=bool(args.recovery), snapshot_every_steps=2,
            max_retries=2))
    sess, *_ = parallax.parallel_run(simple.build_model(0.1),
                                     parallax_config=cfg)
    sess.prepare(_batch_for(0))
    i = sess.data_cursor
    while i < args.steps:
        sess.run("loss", feed_dict=_batch_for(i, nan=i in nan_at))
        if args.crash_at >= 0 and i + 1 >= args.crash_at:
            os.kill(os.getpid(), signal.SIGKILL)  # no cleanup, ever
        i += 1
    # the account as of run end: the parent joins this end stamp with
    # the spawn epoch it injected to measure the true wall
    doc = {
        "account": sess.ops_account(),
        "alerts": (sess.alerts.summary()
                   if sess.alerts is not None else None),
        "journal_events": (sess.journal.seq
                           if sess.journal is not None else 0),
        "t_end": time.time(),
    }
    with open(args.out, "w") as f:
        json.dump(doc, f, default=str)
    sess.close()
    return 0


# ---------------------------------------------------------------------------
# parent: orchestrate the phases
# ---------------------------------------------------------------------------

def _run_child(out, ckpt_dir="", flight_dir="", journal="",
               crash_at=-1, nan_at="", recovery=False, env=None,
               timeout=300.0, steps=STEPS):
    """Spawn one training child; stamps PARALLAX_RUN_EPOCH at spawn
    (what the launcher does for real workers) and returns
    ``(proc, spawn_epoch)``."""
    cmd = [sys.executable, os.path.abspath(__file__), "--child",
           "--out", out, "--ckpt-dir", ckpt_dir,
           "--flight-dir", flight_dir, "--journal", journal,
           "--steps", str(steps), "--crash-at", str(crash_at),
           "--nan-at", nan_at]
    if recovery:
        cmd.append("--recovery")
    spawn_epoch = time.time()
    full_env = dict(os.environ, JAX_PLATFORMS="cpu",
                    PARALLAX_RUN_EPOCH=f"{spawn_epoch:.6f}")
    full_env.setdefault(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=8")
    full_env.update(env or {})
    return subprocess.run(cmd, env=full_env, timeout=timeout,
                          capture_output=True, text=True), spawn_epoch


def _read_doc(path) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return {}


def _sum_check(acct) -> dict:
    """The by-construction invariant plus the inner-class view."""
    badput = acct.get("badput_s") or {}
    total = acct.get("productive_s", 0.0) + sum(badput.values())
    return {
        "wall_s": acct.get("wall_s"),
        "accounted_s": round(total, 6),
        "exact": abs(total - acct.get("wall_s", 0.0)) < 1e-4,
    }


def measure(steps: int = STEPS) -> dict:
    result: dict = {"steps": steps, "ckpt_every": CKPT_EVERY,
                    "tolerance": WALL_TOLERANCE}
    work = tempfile.mkdtemp(prefix="goodput_guard_")

    # -- phase 1: clean run — sums to wall, zero alerts ----------------
    out1 = os.path.join(work, "clean.json")
    j1 = os.path.join(work, "clean_journal.jsonl")
    p1, epoch1 = _run_child(out1, journal=j1, steps=steps)
    d1 = _read_doc(out1)
    a1 = d1.get("account") or {}
    from parallax_tpu.obs.journal import read_journal
    evs1 = read_journal(j1)  # read after exit: close() journals last
    parent_wall = (d1.get("t_end", 0.0) - epoch1) or None
    result["clean"] = {
        "rc": p1.returncode,
        "sum": _sum_check(a1),
        "parent_wall_s": round(parent_wall, 3) if parent_wall else None,
        "ledger_wall_s": a1.get("wall_s"),
        "wall_rel_err": (round(abs(a1.get("wall_s", 0.0) - parent_wall)
                               / parent_wall, 4)
                         if parent_wall else None),
        "goodput_fraction": a1.get("goodput_fraction"),
        "attempts": a1.get("attempts"),
        "alerts_fired": ((d1.get("alerts") or {}).get("firings_total")
                         if d1.get("alerts") else None),
        "journal_events": len(evs1),
    }

    # -- phase 2: SIGKILL mid-run, ledger spans both attempts ----------
    ck2 = os.path.join(work, "ck_sigkill")
    out2 = os.path.join(work, "sigkill.json")
    j2 = os.path.join(work, "sigkill_journal.jsonl")
    crash_at = CKPT_EVERY * 2 + 1  # past the 2nd checkpoint commit
    p2a, epoch2a = _run_child(out2, ckpt_dir=ck2, journal=j2,
                              crash_at=crash_at, steps=steps)
    p2b, _ = _run_child(out2, ckpt_dir=ck2, journal=j2, steps=steps)
    d2 = _read_doc(out2)
    a2 = d2.get("account") or {}
    badput2 = a2.get("badput_s") or {}
    # the TRUE wall of the whole run: first spawn -> resumed child's
    # end stamp (one wall-clock domain; both stamps are time.time())
    parent_wall2 = (d2.get("t_end", 0.0) - epoch2a) or None
    result["sigkill"] = {
        "crash_rc": p2a.returncode,
        "resume_rc": p2b.returncode,
        "sum": _sum_check(a2),
        "attempts": a2.get("attempts"),
        "parent_wall_s": (round(parent_wall2, 3)
                          if parent_wall2 else None),
        "ledger_wall_s": a2.get("wall_s"),
        "wall_rel_err": (round(abs(a2.get("wall_s", 0.0)
                                   - parent_wall2) / parent_wall2, 4)
                         if parent_wall2 else None),
        "restore_replay_s": badput2.get("restore_replay"),
        "eviction_downtime_s": badput2.get("eviction_downtime"),
        "steps_recorded": a2.get("steps"),
    }

    # -- phase 3: NaN rollback — discarded work in its own class -------
    fl3 = os.path.join(work, "fl_nan")
    out3 = os.path.join(work, "nan.json")
    j3 = os.path.join(work, "nan_journal.jsonl")
    p3, _ = _run_child(out3, flight_dir=fl3, journal=j3, nan_at="6",
                       recovery=True, steps=steps)
    d3 = _read_doc(out3)
    a3 = d3.get("account") or {}
    evs = read_journal(j3)
    kinds = [(e.get("subsystem"), e.get("kind")) for e in evs]
    result["nan"] = {
        "rc": p3.returncode,
        "sum": _sum_check(a3),
        "rollback_discarded_s": (a3.get("badput_s")
                                 or {}).get("rollback_discarded"),
        "journal_kinds": sorted(set(kinds)),
        "rollback_before_discard": _in_order(
            kinds, ("recovery", "nonfinite_rollback"),
            ("ops", "rollback_discarded")),
    }

    return result


def _in_order(kinds, first, second) -> bool:
    try:
        return kinds.index(first) < kinds.index(second)
    except ValueError:
        return False


def check(result: dict) -> list:
    """-> list of violated invariants (empty = pass)."""
    bad = []
    tol = result["tolerance"]
    c = result["clean"]
    if c["rc"] != 0:
        bad.append(f"clean run failed rc={c['rc']}")
    if not c["sum"]["exact"]:
        bad.append(f"clean account does not sum to wall: "
                   f"{c['sum']}")
    if c["wall_rel_err"] is None or c["wall_rel_err"] > tol:
        bad.append(f"clean ledger wall {c['ledger_wall_s']}s vs "
                   f"parent-measured {c['parent_wall_s']}s: relative "
                   f"error {c['wall_rel_err']} > {tol}")
    if c["alerts_fired"] != 0:
        bad.append(f"clean run fired {c['alerts_fired']} alert(s); "
                   f"a healthy run must fire zero")
    if not c["journal_events"]:
        bad.append("clean run journaled zero events (the session "
                   "close event alone should appear)")
    s = result["sigkill"]
    if s["crash_rc"] != -signal.SIGKILL:
        bad.append(f"sigkill child exited {s['crash_rc']}, not "
                   f"-SIGKILL — the crash never happened")
    if s["resume_rc"] != 0:
        bad.append(f"sigkill resume failed rc={s['resume_rc']}")
    if s["attempts"] != 2:
        bad.append(f"resumed ledger reports attempts="
                   f"{s['attempts']}, expected 2 — the account did "
                   f"not persist through the checkpoint manifest")
    if not s["sum"]["exact"]:
        bad.append(f"resumed account does not sum to wall: "
                   f"{s['sum']}")
    if not s["restore_replay_s"] or s["restore_replay_s"] <= 0:
        bad.append(f"restore_replay badput is "
                   f"{s['restore_replay_s']!r}; the restore-verify "
                   f"wall must be attributed")
    if not s["eviction_downtime_s"] or s["eviction_downtime_s"] <= 0:
        bad.append(f"eviction_downtime badput is "
                   f"{s['eviction_downtime_s']!r}; the save->respawn "
                   f"gap must be attributed")
    if s["wall_rel_err"] is None or s["wall_rel_err"] > tol:
        bad.append(f"cross-attempt ledger wall {s['ledger_wall_s']}s "
                   f"vs parent-measured {s['parent_wall_s']}s: "
                   f"relative error {s['wall_rel_err']} > {tol}")
    n = result["nan"]
    if n["rc"] != 0:
        bad.append(f"NaN-rollback run failed rc={n['rc']}")
    if not n["rollback_discarded_s"] or n["rollback_discarded_s"] <= 0:
        bad.append(f"rollback_discarded badput is "
                   f"{n['rollback_discarded_s']!r}; discarded step "
                   f"time must land in its own class")
    if ("recovery", "nonfinite_rollback") not in n["journal_kinds"]:
        bad.append(f"journal carries no recovery/nonfinite_rollback "
                   f"event (got {n['journal_kinds']})")
    if ("ops", "rollback_discarded") not in n["journal_kinds"]:
        bad.append(f"journal carries no ops/rollback_discarded event "
                   f"(got {n['journal_kinds']})")
    if not n["rollback_before_discard"]:
        bad.append("journal order broken: the rollback event must "
                   "precede its discard accounting")
    return bad


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--child", action="store_true")
    ap.add_argument("--out", default="")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--flight-dir", default="")
    ap.add_argument("--journal", default="")
    ap.add_argument("--steps", type=int, default=STEPS)
    ap.add_argument("--crash-at", type=int, default=-1)
    ap.add_argument("--nan-at", default="")
    ap.add_argument("--recovery", action="store_true")
    args = ap.parse_args(argv)
    if args.child:
        return child_main(args)
    result = measure(steps=args.steps)
    violations = check(result)
    result["violations"] = violations
    result["ok"] = not violations
    print(json.dumps(result, indent=2, default=str))
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
