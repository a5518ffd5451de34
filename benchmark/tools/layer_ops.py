#!/usr/bin/env python3
"""A training cell's device operations by layer: the per-layer account
(``lib/layer_account``) taken apart, operation by operation.

    python3 benchmark/tools/layer_ops.py --workload <cell> [--seed N]
        [--steps 3] [--top 12] [--out FILE] [--hlo FILE]

``breakdown.device_ops`` of a traced run lists the ten largest
operations of the whole step under the trace's own names; this lists,
for every layer of ``session.layer_index()``, its ``--top`` largest with
the milliseconds a step, the executions a step, the opcode and the
result shape, and the ``op_name`` the program gave the instruction. It
builds the cell as ``run.py`` does (builder, generator, warm-up), runs
``--steps`` steps under the profiler, and reduces the trace with the
benchmark's own reduction. On the chip only; by hand, never by the
driver. ``--out`` also writes the table as JSON (every operation of a
layer with ``--top 0``), ``--hlo`` the optimized HLO text of the step
that ran, for a look inside a fusion.
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
for p in (BENCH_DIR, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--top", type=int, default=12)
    ap.add_argument("--out")
    ap.add_argument("--hlo")
    args = ap.parse_args()

    from lib import cell as cell_lib, device as device_lib
    cell = cell_lib.resolve(args.workload)
    device_lib.require(cell.chips, rehearse=False)

    import jax
    from parallax_tpu.compile.cache import ensure_persistent_cache
    ensure_persistent_cache()
    system = cell.plugin("builders", cell.config["builder"]).build(
        cell, seed=args.seed)
    feeds = cell.plugin("generators", cell.traffic["generator"]).make(
        cell.mix, seed=args.seed, vocab_size=system.vocab_size)
    sess = system.session
    sess.warmup(feed_dict=feeds[0])
    for i in range(2):
        float(sess.run("loss", feed_dict=feeds[i % len(feeds)]))

    from lib import layer_account, tracing
    from reduce import xplane
    work = os.path.join(ROOT, "benchmark_out", "_layer_ops")
    tracing.start_profiler(work)
    for i in range(args.steps):
        float(sess.run("loss", feed_dict=feeds[i % len(feeds)]))
    jax.block_until_ready(sess.state.params)
    tracing.stop_profiler()
    index = sess.layer_index()
    if args.hlo:
        os.makedirs(os.path.dirname(os.path.abspath(args.hlo)), exist_ok=True)
        with open(args.hlo, "w") as f:
            f.write(sess.engine.executable_text())
    snapshot = sess.metrics_snapshot()
    sess.close()
    peak = device_lib.memory_peak_bytes(jax.devices())

    trace = xplane.read(tracing.newest_xplane(work))
    lo, hi = xplane.window(trace)
    ordinal, ops = sorted(trace.devices.items())[0]
    runs = trace.modules.get(ordinal) or []
    steps = len(xplane.runs_of(runs, index["module"], lo, hi)) or args.steps
    own = xplane.self_times(ops, lo, hi)
    calls = collections.Counter(op.name for op in ops if lo <= op.start < hi)
    rows = collections.defaultdict(list)
    for event, seconds in own.items():
        name, opcode, result = xplane.parse_instruction(event)
        layer = index["layers"].get(name, layer_account.UNKNOWN) \
            or layer_account.UNSCOPED
        meta = index["hlo_index"].get(name, {})
        rows[layer].append({
            "name": name, "opcode": opcode, "result": result[:70],
            "ms_per_step": 1e3 * seconds / steps,
            "calls_per_step": calls[event] / steps,
            "op_name": (meta.get("op_name") or "")[-110:]})
    table = {}
    for layer, items in sorted(rows.items(), key=lambda kv: -sum(
            r["ms_per_step"] for r in kv[1])):
        items.sort(key=lambda r: -r["ms_per_step"])
        total = sum(r["ms_per_step"] for r in items)
        top = items[:args.top] if args.top else items
        table[layer] = {"ms_per_step": total, "operations": len(items),
                        "top": top}
        print(f"== {layer}: {total:.2f} ms a step, {len(items)} operations")
        for r in top[:40]:
            print(f"  {r['ms_per_step']:8.3f} ms x{r['calls_per_step']:6.1f}"
                  f"  {r['name']} {r['opcode']} {r['result']}  "
                  f"<{r['op_name']}>")
    gauges = {k: v for k, v in snapshot.items()
              if k.startswith(("moe.", "sparse_attn."))}
    print(json.dumps({"steps": steps, "gauges": gauges,
                      "memory_peak_bytes": peak}))
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"workload": cell.name, "steps": steps,
                       "gauges": gauges, "layers": table}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
