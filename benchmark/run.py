#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process each time: load, warm up every shape the cell uses (all
of that is ``setup_s``, less the seconds JAX's import and the TPU
runtime's start-up took), measure for ``--seconds``, check the outputs,
print one JSON object as the last line of standard output, exit. What a
cell is comes from data: ``BENCHMARK.json`` names the workload's
configuration and traffic; ``benchmark/configs/<config>.json`` names
the kind of run and the builder; ``benchmark/traffic/<mix>.json`` names
the generator; each per-layer metric has a reader of its name under
``benchmark/layer_metrics/``. See ``benchmark/README.md``.

With ``--trace 0`` the line holds the cell's end-to-end metrics, taken
with the profiler off. With ``--trace 1`` the profiler runs for a few
seconds in the middle of the window and the line holds the per-layer
metrics, the device's busy seconds and the breakdown.

Off the TPU this exits non-zero before compiling and prints no result.
``--rehearse-cpu`` (never passed by the driver) runs the same control
flow at tiny sizes with the kernels interpreted, says it is a
rehearsal, and prints counts only.
"""

from __future__ import annotations

import time

CLOCK_START = time.perf_counter()       # set-up is counted from here

import argparse                          # noqa: E402
import json                              # noqa: E402
import math                              # noqa: E402
import os                                # noqa: E402
import sys                               # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
for p in (BENCH_DIR, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)


def log(msg: str) -> None:
    print(f"benchmark[{time.perf_counter() - CLOCK_START:7.1f}s] {msg}",
          file=sys.stderr, flush=True)


def _jsonable(x):
    if isinstance(x, dict):
        return {str(k): _jsonable(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [_jsonable(v) for v in x]
    if isinstance(x, float) and not math.isfinite(x):
        return None
    if hasattr(x, "item") and not isinstance(x, (str, bytes)):
        try:
            return _jsonable(x.item())
        except (ValueError, AttributeError):
            return str(x)
    if isinstance(x, (int, float, str, bool)) or x is None:
        return x
    return str(x)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="tiny sizes off the chip, kernels interpreted; "
                         "prints counts only and says it is a rehearsal")
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(ROOT, "parallax_tpu")):
        sys.exit("benchmark: no parallax_tpu/ beside benchmark/: the "
                 "system under test is not in this checkout")

    from lib import cell as cell_lib

    cell = cell_lib.resolve(args.workload, rehearse=args.rehearse_cpu)

    from lib import device as device_lib

    t = time.perf_counter()
    devices = device_lib.require(cell.chips, args.rehearse_cpu)
    # Importing JAX and bringing up the TPU runtime is neither the
    # program's work nor the benchmark's, and on one machine it took 9
    # to 14 s from run to run with nothing changed (PERF.md, PR 23):
    # set-up is counted without it, and it is on the detail line.
    backend_start_s = time.perf_counter() - t
    clock_start = CLOCK_START + backend_start_s
    stamp = device_lib.stamp(devices)
    peaks = None if args.rehearse_cpu else device_lib.peaks(stamp["kind"])
    log(f"cell {cell.name}: config {cell.config_name}, traffic "
        f"{cell.traffic_name}, {stamp}")

    from parallax_tpu.compile.cache import ensure_persistent_cache

    cache_dir = ensure_persistent_cache()

    def cache_entries() -> int:
        try:
            return len(os.listdir(cache_dir))
        except FileNotFoundError:
            return 0

    entries_before = cache_entries()
    out_dir = os.path.join(
        ROOT, "benchmark_out", cell.name,
        f"seed{args.seed}-trace{args.trace}"
        + ("-rehearsal" if args.rehearse_cpu else ""))
    os.makedirs(out_dir, exist_ok=True)

    from lib import layers, tracing

    trace_window = None
    if args.trace:
        trace_s = min(float(cell.mix.get("trace_seconds", 3.0)),
                      0.6 * args.seconds)
        trace_window = tracing.TraceWindow(
            out_dir, start_at=0.5 * (args.seconds - trace_s),
            seconds=trace_s)

    kind = cell.plugin("kinds", cell.config["kind"])
    result = kind.run(cell, args, clock_start, trace_window, log)
    run = result.pop("context")
    log(f"window {run['window_s']:.2f}s, attempted {result['attempted']}, "
        f"failed {result['failed']}, correct {result['correct']}")

    device = dict(stamp)
    device["memory_peak_bytes"] = device_lib.memory_peak_bytes(devices)
    measured = dict(result["end_to_end"])
    measured["setup_s"] = result["setup_s"]
    breakdown = None
    if args.trace:
        t = time.perf_counter()
        path = trace_window.xplane_path()
        trace = None
        if path is not None:
            from reduce import xplane
            trace = xplane.read(path)
        spans = tracing.spans_between(trace_window.t_sync,
                                      trace_window.t_end) \
            if trace_window.done else []
        ctx = layers.Context(cell, run, device, peaks, trace_window,
                             trace, spans)
        device.update(layers.device_block(ctx))
        breakdown = layers.breakdown(ctx)
        measured = {}
        for m in cell.per_layer:
            value = cell.plugin("layer_metrics", m["name"]).read(ctx)
            if value is not None:
                measured[m["name"]] = float(value)
        log(f"trace reduced in {time.perf_counter() - t:.1f}s "
            f"({path}, profiler stop took {trace_window.stop_seconds}s)")
        declared = cell.per_layer
    else:
        declared = cell.end_to_end

    units = {m["name"]: m["unit"] for m in declared}
    metrics = {name: {"value": measured[name], "unit": units[name]}
               for name in units
               if measured.get(name) is not None
               and math.isfinite(measured[name])}

    detail = {
        "workload": cell.name, "seed": args.seed, "seconds": args.seconds,
        "trace": args.trace, "rehearsal": bool(args.rehearse_cpu),
        "window_s": run["window_s"], "setup_s": result["setup_s"],
        "backend_start_s": backend_start_s,
        "whole_window": result["whole_window"],
        "checks": result["checks"],
        "compile_cache": {"dir": cache_dir,
                          "entries_before": entries_before,
                          "entries_after": cache_entries()},
        "run_seconds_total": time.perf_counter() - CLOCK_START,
    }
    if args.rehearse_cpu:
        # counts and checks only, and no number under a metric's name: a
        # CPU's times are nobody's numbers
        for key in ("window_s", "setup_s", "backend_start_s",
                    "whole_window", "run_seconds_total"):
            detail.pop(key)
        print("benchmark-detail " + json.dumps(_jsonable(detail)),
              flush=True)
        print(json.dumps(_jsonable({
            "rehearsal": True, "correct": result["correct"],
            "attempted": result["attempted"], "failed": result["failed"],
            "metrics_a_chip_run_would_print": sorted(units),
            "metrics_read": sorted(metrics), "device": stamp})),
            flush=True)
        return 0 if result["correct"] else 1

    print("benchmark-detail " + json.dumps(_jsonable(detail)), flush=True)
    line = {"correct": bool(result["correct"]),
            "attempted": int(result["attempted"]),
            "failed": int(result["failed"]),
            "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    print(json.dumps(_jsonable(line)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
