#!/usr/bin/env python3
"""Record the small device trace that ``benchmark/tests`` check the
reduction against, and print what a trace on this machine looks like.

    python3 benchmark/tools/record_test_trace.py [--out DIR]

A few runs of a tiny program on every device JAX reports: matrix
products, one Mosaic kernel (the LSTM recurrence at a toy size) and,
with several devices, one all-reduce, between the benchmark's
``bench.sync`` and ``bench.end`` marks, with an idle gap of known length
in the middle. It writes ``<n>chip.xplane.pb`` and
``<n>chip.structure.txt`` (planes, lines, event counts, the first events
of each line with their stats) into ``--out`` (default
``chiprun_out/test_trace``). Look at the structure by hand before
changing ``reduce/xplane.py``.
"""

from __future__ import annotations

import argparse
import os
import shutil
import sys
import time

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
for p in (BENCH_DIR, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out",
                                                  "test_trace"))
    ap.add_argument("--gap-seconds", type=float, default=0.05)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from parallax_tpu.ops import pallas_lstm

    devices = jax.devices()
    n = len(devices)
    mesh = Mesh(np.array(devices), ("d",))
    sharded = NamedSharding(mesh, P("d"))

    T, B, E, H, Pj = 4, 128, 128, 256, 128
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    bf = jnp.bfloat16
    x = (jax.random.normal(ks[0], (T, B, E)) * 0.05).astype(bf)
    w = (jax.random.normal(ks[1], (E + Pj, 4 * H)) * 0.05).astype(bf)
    b = jnp.zeros((4 * H,), bf)
    wp = (jax.random.normal(ks[2], (H, Pj)) * 0.05).astype(bf)

    @jax.jit
    def kernel_step(x, w, b, wp):
        hs = pallas_lstm.lstm_scan(x, w, b, wp, impl="pallas")
        return jnp.sum(hs.astype(jnp.float32))

    a = jax.device_put(jnp.ones((n * 512, 1024), bf), sharded)

    @jax.jit
    def dense_step(a):
        z = jnp.tanh(a @ jnp.ones((1024, 1024), bf))
        # a sum over the sharded rows: one all-reduce across the devices
        return jnp.sum(z.astype(jnp.float32), axis=0)

    for _ in range(2):                     # compile outside the trace
        kernel_step(x, w, b, wp).block_until_ready()
        dense_step(a).block_until_ready()

    from lib import tracing

    work = os.path.join(args.out, "_profile")
    t_sync = tracing.start_profiler(work)
    for i in range(4):
        kernel_step(x, w, b, wp).block_until_ready()
        dense_step(a).block_until_ready()
        if i == 1:
            time.sleep(args.gap_seconds)   # an idle gap of known length
    t_end = tracing.stop_profiler()

    os.makedirs(args.out, exist_ok=True)
    pb = os.path.join(args.out, f"{n}chip.xplane.pb")
    shutil.copyfile(tracing.newest_xplane(work), pb)
    shutil.rmtree(work, ignore_errors=True)

    from jax.profiler import ProfileData
    lines = [f"devices: {n} x {devices[0].device_kind!r} "
             f"({devices[0].platform}); window {t_end - t_sync:.4f}s; "
             f"gap {args.gap_seconds}s; file {os.path.getsize(pb)} bytes"]
    for plane in ProfileData.from_file(pb).planes:
        lines.append(f"PLANE {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            lines.append(f"  LINE {line.name!r}: {len(events)} events")
            for ev in events[:12]:
                lines.append(
                    f"    {ev.name!r} start_ns={ev.start_ns:.0f} "
                    f"dur_ns={ev.duration_ns:.0f} "
                    f"stats={dict(list(ev.stats)[:12])}")
    text = "\n".join(lines)
    with open(os.path.join(args.out, f"{n}chip.structure.txt"), "w") as f:
        f.write(text + "\n")
    print(text[:6000])

    from reduce import xplane
    trace = xplane.read(pb)
    lo_hi = xplane.window(trace)
    print("reduced: devices", sorted(trace.devices), "window", lo_hi)
    if lo_hi and trace.devices:
        lo, hi = lo_hi
        for d, ops in sorted(trace.devices.items()):
            busy = xplane.busy(ops, lo, hi)
            print(f"  device {d}: {len(ops)} ops, busy "
                  f"{sum(e - s for s, e in busy):.6f}s of {hi - lo:.6f}s, "
                  f"mosaic {xplane.category_seconds(ops, 'mosaic', lo, hi):.6f}s "
                  f"({xplane.count(ops, 'mosaic', lo, hi)}), collective "
                  f"{xplane.category_seconds(ops, 'collective', lo, hi):.6f}s "
                  f"({xplane.count(ops, 'collective', lo, hi)}), longest gap "
                  f"{max((b - a for a, b in xplane.idle_gaps(busy, lo, hi)), default=0):.6f}s")
    return 0


if __name__ == "__main__":
    sys.exit(main())
