#!/usr/bin/env python3
"""Run cells as the driver does and read the spread: for each cell two
sets of runs, each run a new process with another ``--seed``, then the
median and the spread (the distance between the quartiles over the
median) of every end-to-end metric in each set.

    python3 benchmark/tools/measure.py --workloads a,b [--sets 2] [--runs 6] [--seconds <run_seconds>] [--traced 1]

This process never touches JAX: the chip belongs to the run it starts.
Every run's last line is kept in ``chiprun_out/measure/<cell>.jsonl``
and the summary in ``chiprun_out/measure/<cell>.summary.json``. A bound
is about five times the widest spread a metric shows over the cells,
never under 1 %; ``setup_s`` is judged by its median alone, and the
first run of a cell in a checkout compiles and is kept apart.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, BENCH_DIR)

from lib import stats  # noqa: E402


def one_run(command, workload, seed, seconds, trace, log_dir):
    t = time.time()
    argv = list(command) + ["--workload", workload, "--seed", str(seed),
                            "--seconds", str(seconds), "--trace", str(trace)]
    proc = subprocess.run(argv, cwd=ROOT, capture_output=True, text=True)
    took = time.time() - t
    out_lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    line = None
    if proc.returncode == 0 and out_lines:
        try:
            line = json.loads(out_lines[-1])
        except ValueError:
            line = None
    with open(os.path.join(log_dir, f"{workload}.seed{seed}.trace{trace}"
                                    ".log"), "w") as f:
        f.write(proc.stdout[-20000:] + "\n--- stderr ---\n"
                + proc.stderr[-20000:])
    return {"workload": workload, "seed": seed, "trace": trace,
            "rc": proc.returncode, "seconds_taken": took, "line": line,
            "detail": next((json.loads(ln[len("benchmark-detail "):])
                            for ln in out_lines
                            if ln.startswith("benchmark-detail ")), None)}


def main() -> int:
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    ap = argparse.ArgumentParser()
    ap.add_argument("--workloads", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--runs", type=int, default=6)
    ap.add_argument("--seconds", type=int, default=bench["run_seconds"])
    ap.add_argument("--traced", type=int, default=0,
                    help="traced runs after the sets")
    ap.add_argument("--first-seed", type=int, default=100)
    args = ap.parse_args()

    out_dir = os.path.join(ROOT, "chiprun_out", "measure")
    os.makedirs(out_dir, exist_ok=True)
    for workload in args.workloads.split(","):
        runs = []
        seed = args.first_seed
        for s in range(args.sets):
            for _ in range(args.runs):
                seed += 1
                r = one_run(bench["command"], workload, seed, args.seconds,
                            0, out_dir)
                r["set"] = s + 1
                runs.append(r)
                print(f"{workload} set {s + 1} seed {seed} rc {r['rc']} "
                      f"{r['seconds_taken']:.0f}s "
                      + json.dumps(r["line"])[:400], flush=True)
        for _ in range(args.traced):
            seed += 1
            r = one_run(bench["command"], workload, seed, args.seconds, 1,
                        out_dir)
            r["set"] = "traced"
            runs.append(r)
            print(f"{workload} traced seed {seed} rc {r['rc']} "
                  f"{r['seconds_taken']:.0f}s " + json.dumps(r["line"]),
                  flush=True)
        with open(os.path.join(out_dir, workload + ".jsonl"), "a") as f:
            for r in runs:
                f.write(json.dumps(r) + "\n")

        summary = {"workload": workload, "seconds": args.seconds,
                   "metrics": {}}
        timed = [r for r in runs if r["trace"] == 0 and r["line"]]
        names = sorted({n for r in timed for n in r["line"]["metrics"]})
        for name in names:
            per_set = []
            for s in range(args.sets):
                vals = [r["line"]["metrics"][name]["value"] for r in timed
                        if r["set"] == s + 1 and name in r["line"]["metrics"]]
                if name == "setup_s" and s == 0:
                    vals = vals[1:]     # the first run of a checkout compiles
                per_set.append({"values": vals,
                                "median": stats.median(vals),
                                "spread": stats.quartile_spread(vals)})
            spreads = [p["spread"] for p in per_set if p["spread"] is not None]
            summary["metrics"][name] = {
                "sets": per_set,
                "widest_spread": max(spreads) if spreads else None}
        summary["correct"] = [r["line"]["correct"] if r["line"] else None
                              for r in runs]
        summary["failed"] = [r["line"]["failed"] if r["line"] else None
                             for r in runs]
        summary["memory_peak_bytes"] = [
            r["line"]["device"].get("memory_peak_bytes")
            if r["line"] else None for r in runs]
        with open(os.path.join(out_dir, workload + ".summary.json"),
                  "w") as f:
            json.dump(summary, f, indent=1)
        print("summary " + json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
