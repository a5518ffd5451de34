"""Training chaos guard: SIGKILL, torn saves, NaN bursts — gated.

ISSUE 9 acceptance, enforced in tier-1
(tests/test_ckpt.py::test_train_chaos_guard via the established
subprocess-driver pattern) and runnable directly::

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python tools/check_train_faults.py

Four phases, each over the deterministic simple-model training loop
(batch *i* is a pure function of *i*, so any two runs that agree on
state + cursor agree on every loss bit):

* **baseline** — N uninterrupted steps; the per-step losses (recorded
  as ``float.hex()``) are the bit-identity reference.
* **sigkill** — a worker trains with checkpoints every k steps and
  SIGKILLs itself mid-run (no atexit, no flushing — the hardware
  failure model). The relaunched worker restores the last committed
  checkpoint, skips ``data_cursor`` batches of the same stream, and
  finishes. Contract: every post-resume loss is BIT-identical to the
  uninterrupted run, and the resumed worker leaves a ``resume``
  flight artifact.
* **torn** — the worker dies INSIDE a checkpoint save, after the
  shard files are durable but before the manifest commit
  (``PARALLAX_CKPT_FAULT=torn_manifest``). The relaunch must detect
  the torn directory, fall back to the previous complete checkpoint
  with a loud log + ``ckpt_torn`` flight artifact, and still finish
  bit-identical to the uninterrupted run.
* **nan** — a NaN batch is injected with auto-recovery enabled
  (``RecoveryConfig``): the worker must roll back to its in-memory
  last-good snapshot, skip the offending batch, finish ALL remaining
  batches with a finite final loss and no human intervention, and
  leave a ``nonfinite_rollback`` flight artifact. A second injection
  run with every batch poisoned must SURRENDER within the bounded
  retry budget (``recovery_surrender`` artifact, nonzero exit).
* **preemption** — the parent SIGTERMs a mid-training worker; the
  worker's handler leaves a ``preemption`` flight artifact and ONE
  final checkpoint at its current step before dying with the
  standard SIGTERM status.

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


# ---------------------------------------------------------------------------
# child: one deterministic training run
# ---------------------------------------------------------------------------

def _batch_for(i: int, nan: bool = False):
    import numpy as np
    from parallax_tpu.models import simple
    b = simple.make_batch(np.random.default_rng(1000 + i), 32)
    if nan:
        b["x"] = b["x"] * np.nan
    return b


def child_main(args) -> int:
    import numpy as np  # noqa: F401

    import parallax_tpu as parallax
    from parallax_tpu.models import simple

    nan_at = {int(s) for s in args.nan_at.split(",") if s}
    cfg = parallax.Config(
        run_option="AR", search_partitions=False,
        flight_dir=args.flight_dir or None,
        ckpt_config=parallax.CheckPointConfig(
            ckpt_dir=args.ckpt_dir or None,
            save_ckpt_steps=CKPT_EVERY if args.ckpt_dir else None),
        recovery_config=parallax.RecoveryConfig(
            enabled=bool(args.recovery), snapshot_every_steps=2,
            max_retries=2),
        # numerics provenance only on the recovery phases: the
        # sigkill/torn phases compare losses bit-exactly against the
        # uninstrumented baseline, so their graphs must stay identical
        numerics_interval=2 if args.recovery else 0)
    sess, *_ = parallax.parallel_run(simple.build_model(0.1),
                                     parallax_config=cfg)
    start = sess.prepare(_batch_for(0))
    cursor = sess.data_cursor
    with open(args.out, "a") as f:
        f.write(f"# start={start} cursor={cursor}\n")
    i = cursor
    while i < args.steps:
        batch = _batch_for(i, nan=i in nan_at)
        loss = sess.run("loss", feed_dict=batch)
        val = float(loss)
        # losses keyed by BATCH index (the cursor), hex-exact: a NaN
        # rollback rewinds the step counter but never the cursor, so
        # the cursor is the only stable join key across runs
        with open(args.out, "a") as f:
            f.write(f"{i} {val.hex()}\n")
            f.flush()
        if args.crash_at >= 0 and i + 1 >= args.crash_at:
            os.kill(os.getpid(), signal.SIGKILL)  # no cleanup, ever
        if args.hang_after >= 0 and i + 1 >= args.hang_after:
            # park for the parent's SIGTERM (preemption phase)
            while True:
                time.sleep(0.1)
        i += 1
    with open(args.out, "a") as f:
        f.write(f"# done step={sess._host_step} "
                f"cursor={sess.data_cursor} "
                f"rollbacks={sess._recovery.total_rollbacks if sess._recovery else 0}\n")
    sess.close()
    return 0


# ---------------------------------------------------------------------------
# parent: orchestrate the phases
# ---------------------------------------------------------------------------

def _run_child(out, ckpt_dir="", flight_dir="", crash_at=-1,
               nan_at="", recovery=False, hang_after=-1, env=None,
               timeout=300.0, steps=STEPS):
    cmd = [sys.executable, os.path.abspath(__file__), "--child",
           "--out", out, "--ckpt-dir", ckpt_dir,
           "--flight-dir", flight_dir, "--steps", str(steps),
           "--crash-at", str(crash_at), "--nan-at", nan_at,
           "--hang-after", str(hang_after)]
    if recovery:
        cmd.append("--recovery")
    full_env = dict(os.environ, JAX_PLATFORMS="cpu")
    full_env.setdefault(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=8")
    full_env.update(env or {})
    return subprocess.run(cmd, env=full_env, timeout=timeout,
                          capture_output=True, text=True)


def _read_losses(path) -> dict:
    """{batch index: loss hex} plus the '#' metadata lines."""
    out, meta = {}, []
    try:
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                if line.startswith("#"):
                    meta.append(line)
                    continue
                i, hx = line.split()
                out[int(i)] = hx
    except OSError:
        pass
    return {"losses": out, "meta": meta}


def _flight_classes(flight_dir) -> list:
    try:
        return sorted({os.path.basename(p).split("_", 1)[1]
                       .rsplit("_", 2)[0]
                       for p in os.listdir(flight_dir)})
    except OSError:
        return []


def measure(steps: int = STEPS) -> dict:
    result: dict = {"steps": steps, "ckpt_every": CKPT_EVERY}
    work = tempfile.mkdtemp(prefix="train_faults_")

    # -- baseline: uninterrupted ---------------------------------------
    base_out = os.path.join(work, "baseline.losses")
    t0 = time.perf_counter()
    p = _run_child(base_out, steps=steps)
    result["baseline"] = {
        "rc": p.returncode,
        "seconds": round(time.perf_counter() - t0, 3),
    }
    baseline = _read_losses(base_out)["losses"]
    result["baseline"]["recorded"] = len(baseline)

    # -- phase 1: SIGKILL mid-run, exact resume ------------------------
    ck1 = os.path.join(work, "ck_sigkill")
    fl1 = os.path.join(work, "fl_sigkill")
    out1 = os.path.join(work, "sigkill.losses")
    crash_at = CKPT_EVERY * 2 + 1  # past the 2nd checkpoint commit
    p1 = _run_child(out1, ckpt_dir=ck1, flight_dir=fl1,
                    crash_at=crash_at, steps=steps)
    t0 = time.perf_counter()
    p1b = _run_child(out1, ckpt_dir=ck1, flight_dir=fl1, steps=steps)
    r1 = _read_losses(out1)
    resumed_from = None
    for m in r1["meta"]:
        if "start=" in m and "start=0" not in m:
            resumed_from = int(m.split("start=")[1].split()[0])
    mism1 = [i for i, hx in r1["losses"].items()
             if baseline.get(i) != hx]
    result["sigkill"] = {
        "crash_rc": p1.returncode,
        "resume_rc": p1b.returncode,
        "resume_seconds": round(time.perf_counter() - t0, 3),
        "crash_at_batch": crash_at,
        "resumed_from_step": resumed_from,
        "recorded": len(r1["losses"]),
        "loss_mismatches": mism1,
        "flight_classes": _flight_classes(fl1),
    }

    # -- phase 2: crash mid-checkpoint-write (torn manifest) -----------
    ck2 = os.path.join(work, "ck_torn")
    fl2 = os.path.join(work, "fl_torn")
    out2 = os.path.join(work, "torn.losses")
    # the injected fault kills the SECOND save (step 8) mid-commit:
    # the env knob arms every save, so let the first one through by
    # arming only the child that will reach step 8 — simplest is to
    # arm from the start and crash on the FIRST save, leaving zero
    # complete checkpoints... instead we want a fallback target, so:
    # run once cleanly to step 5 (commit at 4), then run armed (the
    # step-8 save dies mid-commit), then resume.
    p2a = _run_child(out2, ckpt_dir=ck2, flight_dir=fl2,
                     crash_at=CKPT_EVERY + 1, steps=steps)
    p2b = _run_child(out2, ckpt_dir=ck2, flight_dir=fl2, steps=steps,
                     env={"PARALLAX_CKPT_FAULT": "torn_manifest"})
    torn_dirs = sorted(
        d for d in os.listdir(ck2)
        if d.isdigit() and not os.path.exists(
            os.path.join(ck2, d, "manifest.json")))
    t0 = time.perf_counter()
    p2c = _run_child(out2, ckpt_dir=ck2, flight_dir=fl2, steps=steps)
    r2 = _read_losses(out2)
    resumed2 = [int(m.split("start=")[1].split()[0])
                for m in r2["meta"] if "start=" in m]
    mism2 = [i for i, hx in r2["losses"].items()
             if baseline.get(i) != hx]
    result["torn"] = {
        "first_rc": p2a.returncode,
        "torn_rc": p2b.returncode,
        "resume_rc": p2c.returncode,
        "resume_seconds": round(time.perf_counter() - t0, 3),
        "torn_dirs_observed": torn_dirs,
        "starts": resumed2,
        "loss_mismatches": mism2,
        "fallback_logged": "FELL BACK" in (p2c.stderr or "")
                           or "TORN" in (p2c.stderr or ""),
        "flight_classes": _flight_classes(fl2),
    }

    # -- phase 3: injected NaN burst, auto-recovery --------------------
    fl3 = os.path.join(work, "fl_nan")
    out3 = os.path.join(work, "nan.losses")
    t0 = time.perf_counter()
    p3 = _run_child(out3, flight_dir=fl3, nan_at="6", recovery=True,
                    steps=steps)
    r3 = _read_losses(out3)
    rollbacks = 0
    completed = False
    for m in r3["meta"]:
        if "done" in m:
            completed = True
            rollbacks = int(m.split("rollbacks=")[1])
    finite_final = False
    if r3["losses"]:
        last = float.fromhex(r3["losses"][max(r3["losses"])])
        finite_final = last == last and abs(last) != float("inf")
    # NaN provenance: the rollback artifact must NAME the poisoned
    # stage (feed/x — the injected batch), not just record the trip
    provenance = {"culprit": None, "trail_len": 0, "blast_radius": None}
    try:
        arts = sorted(p for p in os.listdir(fl3)
                      if p.startswith("flight_nonfinite_rollback_"))
        if arts:
            with open(os.path.join(fl3, arts[0])) as f:
                doc = json.load(f)
            det = ((doc.get("trigger") or {}).get("detail")
                   or doc.get("detail") or {})
            prov = det.get("provenance") or {}
            provenance = {
                "culprit": prov.get("culprit"),
                "blast_radius": prov.get("blast_radius"),
                "trail_len": len(det.get("stats_trail") or ()),
            }
    except (OSError, ValueError):
        pass
    result["nan"] = {
        "rc": p3.returncode,
        "seconds": round(time.perf_counter() - t0, 3),
        "completed": completed,
        "rollbacks": rollbacks,
        "recorded": len(r3["losses"]),
        "final_loss_finite": finite_final,
        "flight_classes": _flight_classes(fl3),
        "provenance": provenance,
    }
    # poisoned run: every batch NaN -> bounded surrender, nonzero rc
    fl3b = os.path.join(work, "fl_nan_all")
    out3b = os.path.join(work, "nan_all.losses")
    p3b = _run_child(out3b, flight_dir=fl3b,
                     nan_at=",".join(str(i) for i in range(steps)),
                     recovery=True, steps=steps)
    result["nan"]["surrender_rc"] = p3b.returncode
    result["nan"]["surrender_flight"] = _flight_classes(fl3b)
    result["nan"]["surrendered"] = (
        p3b.returncode != 0
        and "RecoverySurrender" in (p3b.stderr or ""))

    # -- phase 4: SIGTERM preemption notice ----------------------------
    ck4 = os.path.join(work, "ck_preempt")
    fl4 = os.path.join(work, "fl_preempt")
    out4 = os.path.join(work, "preempt.losses")
    cmd = [sys.executable, os.path.abspath(__file__), "--child",
           "--out", out4, "--ckpt-dir", ck4, "--flight-dir", fl4,
           "--steps", str(steps), "--crash-at", "-1",
           "--nan-at", "", "--hang-after", str(CKPT_EVERY + 2)]
    env4 = dict(os.environ, JAX_PLATFORMS="cpu")
    env4.setdefault("XLA_FLAGS",
                    "--xla_force_host_platform_device_count=8")
    proc = subprocess.Popen(cmd, env=env4, stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE, text=True)
    deadline = time.time() + 240
    # wait until it is parked mid-training (past the hang step)
    while time.time() < deadline:
        if len(_read_losses(out4)["losses"]) >= CKPT_EVERY + 2:
            break
        if proc.poll() is not None:
            break
        time.sleep(0.1)
    time.sleep(0.3)
    t0 = time.perf_counter()
    proc.send_signal(signal.SIGTERM)
    try:
        proc.wait(timeout=120)
    except subprocess.TimeoutExpired:
        proc.kill()
    from parallax_tpu.ckpt.store import CheckpointStore
    final_steps = CheckpointStore(ck4).complete_steps()
    result["preemption"] = {
        "rc": proc.returncode,
        "react_seconds": round(time.perf_counter() - t0, 3),
        "batches_before_sigterm": len(_read_losses(out4)["losses"]),
        "final_checkpoint_steps": final_steps,
        "flight_classes": _flight_classes(fl4),
    }

    return result


def check(result: dict) -> list:
    """-> list of violated invariants (empty = pass)."""
    bad = []
    if result["baseline"]["rc"] != 0:
        bad.append(f"baseline run failed rc="
                   f"{result['baseline']['rc']}")
    s = result["sigkill"]
    if s["crash_rc"] != -signal.SIGKILL:
        bad.append(f"sigkill child exited {s['crash_rc']}, not "
                   f"-SIGKILL — the crash never happened")
    if s["resume_rc"] != 0:
        bad.append(f"sigkill resume failed rc={s['resume_rc']}")
    if s["resumed_from_step"] is None or s["resumed_from_step"] < 1:
        bad.append(f"sigkill resume did not restore a checkpoint "
                   f"(start={s['resumed_from_step']})")
    if s["loss_mismatches"]:
        bad.append(f"SIGKILL resume broke bit-identity at batches "
                   f"{s['loss_mismatches']}")
    if s["recorded"] != result["steps"]:
        bad.append(f"sigkill phases recorded {s['recorded']}/"
                   f"{result['steps']} losses")
    if "resume" not in s["flight_classes"]:
        bad.append(f"no `resume` flight artifact after SIGKILL "
                   f"recovery (got {s['flight_classes']})")
    t = result["torn"]
    if t["torn_rc"] != 31:
        bad.append(f"torn-save child exited {t['torn_rc']}, not the "
                   f"fault's 31 — the mid-save crash never happened")
    if not t["torn_dirs_observed"]:
        bad.append("the mid-save crash left no torn (manifest-less) "
                   "checkpoint directory")
    if t["resume_rc"] != 0:
        bad.append(f"torn resume failed rc={t['resume_rc']}")
    if t["loss_mismatches"]:
        bad.append(f"torn fallback broke bit-identity at batches "
                   f"{t['loss_mismatches']}")
    if not t["fallback_logged"]:
        bad.append("torn fallback left no loud log line")
    if "ckpt_torn" not in t["flight_classes"]:
        bad.append(f"no `ckpt_torn` flight artifact (got "
                   f"{t['flight_classes']})")
    n = result["nan"]
    if n["rc"] != 0 or not n["completed"]:
        bad.append(f"NaN-burst run did not complete without human "
                   f"intervention (rc={n['rc']})")
    if not (1 <= n["rollbacks"] <= 2):
        bad.append(f"expected 1-2 rollbacks, got {n['rollbacks']}")
    if not n["final_loss_finite"]:
        bad.append("NaN-burst run ended with a non-finite loss")
    if "nonfinite_rollback" not in n["flight_classes"]:
        bad.append(f"no `nonfinite_rollback` flight artifact (got "
                   f"{n['flight_classes']})")
    if n["provenance"]["culprit"] != "feed/x":
        bad.append(f"provenance did not name the poisoned feed "
                   f"(culprit={n['provenance']['culprit']!r}, "
                   f"expected 'feed/x')")
    if n["provenance"]["trail_len"] < 1:
        bad.append("rollback artifact carries no numerics stats trail")
    if not n["surrendered"]:
        bad.append(f"all-NaN run did not surrender within the retry "
                   f"budget (rc={n['surrender_rc']})")
    if "recovery_surrender" not in n["surrender_flight"]:
        bad.append(f"no `recovery_surrender` flight artifact (got "
                   f"{n['surrender_flight']})")
    p = result["preemption"]
    if "preemption" not in p["flight_classes"]:
        bad.append(f"no `preemption` flight artifact after SIGTERM "
                   f"(got {p['flight_classes']})")
    if not p["final_checkpoint_steps"]:
        bad.append("SIGTERM left no final checkpoint")
    if p["rc"] != -signal.SIGTERM:
        bad.append(f"preempted worker exited {p['rc']}, not the "
                   f"standard -SIGTERM status")
    return bad


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--child", action="store_true")
    ap.add_argument("--out", default="")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--flight-dir", default="")
    ap.add_argument("--steps", type=int, default=STEPS)
    ap.add_argument("--crash-at", type=int, default=-1)
    ap.add_argument("--nan-at", default="")
    ap.add_argument("--recovery", action="store_true")
    ap.add_argument("--hang-after", type=int, default=-1)
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
