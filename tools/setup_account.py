#!/usr/bin/env python3
"""Where a benchmark cell's ``setup_s`` goes: one set-up, taken apart.

    python3 tools/setup_account.py --workload <cell> [--seed N]
        [--top 12] [--out FILE] [--chrome FILE] [--rehearse-cpu]

``benchmark/run.py --trace 1`` prints seven readings of set-up
(``setup_program_s`` ... ``setup_model_traces``, ``compile_s_in_window``)
and empties the program's span ring when its traced window starts. This
runs the same set-up (builder, generator, warm-up, warm steps, static
checks: ``benchmark/kinds/train.py``'s lines up to the window, which
this file repeats and must follow) and no window, and prints one JSON
object:

* ``phases``: the host clock around each of the benchmark's own calls,
  which have no span (``kinds/train.py`` is the benchmark's). They add
  up to ``setup_s`` as ``run.py`` defines it: from the process's start,
  less JAX's import and the runtime's start-up, to the window.
* ``readings``: the benchmark's own readers of the cell's ``compile``
  layer on the registry as set-up left it.
* ``spans``: every program span of set-up by name, with its count, its
  seconds and its SELF seconds (less the spans inside it on its
  thread), so that what lies under ``parallax.parallel_run``,
  ``session.prepare``, ``engine.build``, ``engine.classify``,
  ``engine.discover_slices``, ``engine.init_state``, ``session.warmup``,
  ``engine.warmup_compile``, ``engine.lower``, ``engine.compile`` and
  ``session.dispatch`` is named, and what is named by none of them
  shows as its parent's self time.
* ``jax``: the ``jax.trace`` / ``jax.lower`` / ``jax.backend_compile``
  spans summed by ``fun``, the ``--top`` largest: WHICH function's
  trace, lowering and compile (or cache load) the seconds were.

On the chip only, by hand, never by the driver. ``--rehearse-cpu`` runs
the same control flow at the cell's tiny sizes and prints names and
counts only: a CPU's seconds are nobody's numbers. ``--chrome`` also
writes the span ring as a chrome trace.
"""

from __future__ import annotations

import time

CLOCK_START = time.perf_counter()

import argparse                          # noqa: E402
import collections                       # noqa: E402
import json                              # noqa: E402
import os                                # noqa: E402
import sys                               # noqa: E402
import types                             # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_DIR = os.path.join(ROOT, "benchmark")
for p in (BENCH_DIR, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)


def span_account(events):
    """``{name: {"n", "total_s", "self_s"}}`` over ``TraceEvent``s: a
    span's self seconds are its own less those of the spans that lie
    inside it on its thread (containment, as chrome nests them)."""
    rows = collections.defaultdict(lambda: {"n": 0, "total_s": 0.0,
                                            "self_s": 0.0})
    by_thread = collections.defaultdict(list)
    for ev in events:
        by_thread[(ev.tid, ev.thread_name)].append(ev)
    for evs in by_thread.values():
        evs.sort(key=lambda ev: (ev.ts, -ev.dur))
        open_spans = []             # [event, seconds of its children]
        # one more event at the end of time closes what is still open
        for ev in evs + [None]:
            start = float("inf") if ev is None else ev.ts
            while open_spans and open_spans[-1][0].ts \
                    + open_spans[-1][0].dur <= start:
                done, inside = open_spans.pop()
                row = rows[done.name]
                row["n"] += 1
                row["total_s"] += done.dur
                row["self_s"] += max(0.0, done.dur - inside)
                if open_spans:
                    open_spans[-1][1] += done.dur
            if ev is not None:
                open_spans.append([ev, 0.0])
    return dict(rows)


def by_fun(events, top: int):
    sums = collections.defaultdict(float)
    for ev in events:
        if ev.name.startswith("jax."):
            sums[(ev.name, (ev.args or {}).get("fun"))] += ev.dur
    ranked = sorted(sums.items(), key=lambda kv: -kv[1])[:top]
    return [[name, fun, seconds] for (name, fun), seconds in ranked]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--top", type=int, default=12)
    ap.add_argument("--out")
    ap.add_argument("--chrome")
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)

    from lib import cell as cell_lib, device as device_lib
    cell = cell_lib.resolve(args.workload, rehearse=args.rehearse_cpu)
    t = time.perf_counter()
    devices = device_lib.require(cell.chips, args.rehearse_cpu)
    backend_start_s = time.perf_counter() - t

    import jax
    from parallax_tpu.compile.cache import ensure_persistent_cache
    from parallax_tpu.obs import trace
    ensure_persistent_cache()
    builder = cell.plugin("builders", cell.config["builder"])
    generator = cell.plugin("generators", cell.traffic["generator"])

    marks = [("start", CLOCK_START + backend_start_s)]

    def mark(name):
        marks.append((name, time.perf_counter()))

    mark("imports_and_resolve")
    system = builder.build(cell, seed=args.seed)
    mark("builder.build")
    feeds = generator.make(cell.mix, seed=args.seed,
                           vocab_size=system.vocab_size)
    mark("generator.make")
    sess = system.session
    sess.warmup(feed_dict=feeds[0])
    mark("session.warmup")
    for i in range(int(cell.mix.get("warm_steps", 2))):
        float(sess.run("loss", feed_dict=feeds[i % len(feeds)]))
    jax.block_until_ready(sess.state.params)
    mark("warm_steps")
    static_failures = system.static_checks()
    mark("static_checks")
    registry = sess.metrics_snapshot()
    mark("metrics_snapshot")

    events = trace.get_collector().events()
    if args.chrome:
        trace.export_chrome_trace(args.chrome)
    stats = sess.compile_stats()
    sess.close()

    ctx = types.SimpleNamespace(run={"registry_before": registry,
                                     "registry_after": registry})
    readings = {m["name"]: cell.plugin("layer_metrics", m["name"]).read(ctx)
                for m in cell.per_layer if m["layer"] == "compile"}
    phases = {name: at - marks[i][1]
              for i, (name, at) in enumerate(marks[1:])}
    spans = span_account(events)
    out = {"workload": cell.name, "seed": args.seed,
           "device": device_lib.stamp(devices),
           "rehearsal": bool(args.rehearse_cpu),
           "static_failures": static_failures}
    if args.rehearse_cpu:
        out["phases"] = sorted(phases)
        out["readings"] = {k: v for k, v in readings.items()
                           if k.endswith(("_misses", "_traces"))}
        out["spans"] = {name: row["n"] for name, row in sorted(spans.items())}
        out["jax"] = sorted({str(fun) for _, fun, _ in
                             by_fun(events, args.top)})
    else:
        out["setup_s"] = marks[-1][1] - marks[0][1]
        out["backend_start_s"] = backend_start_s
        out["phases"] = phases
        out["readings"] = readings
        out["compile_stats"] = stats
        out["spans"] = dict(sorted(spans.items(),
                                   key=lambda kv: -kv[1]["total_s"]))
        out["jax"] = by_fun(events, args.top)
    text = json.dumps(out)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            f.write(text + "\n")
    print(text, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
