#!/usr/bin/env python3
"""Record the small device trace, with the program's layer index, that
``benchmark/tests/test_layer_account.py`` checks the per-layer account
against.

    python3 benchmark/tools/record_layer_trace.py [--out DIR]

A small LM1B (the kernels' least sizes, a 4,096-word vocabulary) built
as the benchmark builds the real one (``parallax.parallel_run``, slices
on, the Pallas LSTM), warmed up, then ``--steps`` training steps between
the benchmark's ``bench.sync`` and ``bench.end`` marks. It writes
``lm1b-small-layers.xplane.pb.gz`` and
``lm1b-small-layers.layer_index.json.gz`` (``session.layer_index()``
less its ``hlo_index``) into ``--out`` (default
``chiprun_out/layer_trace``) and prints the account. Gzipped, since an
event's name is its whole HLO instruction and 4,000 of them make a
megabyte. Copy the two files to ``benchmark/tests/data/`` by hand.
"""

from __future__ import annotations

import argparse
import gzip
import json
import os
import shutil
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
for p in (BENCH_DIR, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

NAME = "lm1b-small-layers"


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out",
                                                  "layer_trace"))
    ap.add_argument("--steps", type=int, default=3)
    args = ap.parse_args()

    import jax
    import numpy as np

    import parallax_tpu as parallax
    from parallax_tpu.models import lm1b

    n = jax.device_count()
    cfg = lm1b.LM1BConfig(
        vocab_size=4096, emb_dim=128, hidden_dim=256, proj_dim=128,
        num_samples=256, keep_prob=0.9, lstm_impl="pallas",
        sparse_grad_mode="slices", num_partitions=n)
    sess, *_ = parallax.parallel_run(
        lm1b.build_model(cfg), seed=0,
        parallax_config=parallax.Config(
            run_option="HYBRID", search_partitions=False,
            sparse_grad_mode="slices", shape_buckets="auto"))
    rng = np.random.default_rng(0)
    batches = [lm1b.make_batch(rng, 128 * n, 4, cfg.vocab_size)
               for _ in range(2)]
    sess.warmup(feed_dict=batches[0])
    for b in batches:                       # warm outside the trace
        float(sess.run("loss", feed_dict=b))

    from lib import layer_account, tracing
    from reduce import xplane

    work = os.path.join(args.out, "_profile")
    tracing.start_profiler(work)
    for i in range(args.steps):
        float(sess.run("loss", feed_dict=batches[i % 2]))
    jax.block_until_ready(sess.state.params)
    tracing.stop_profiler()

    os.makedirs(args.out, exist_ok=True)
    pb = tracing.newest_xplane(work)
    with open(pb, "rb") as src, gzip.open(
            os.path.join(args.out, NAME + ".xplane.pb.gz"), "wb") as dst:
        shutil.copyfileobj(src, dst)
    index = {k: v for k, v in sess.layer_index().items()
             if k != "hlo_index"}
    with gzip.open(os.path.join(args.out, NAME + ".layer_index.json.gz"),
                   "wt") as f:
        json.dump(index, f, sort_keys=True, separators=(",", ":"))
    sess.close()

    trace = xplane.read(pb)
    print(f"{jax.devices()[0].device_kind!r} x {n}; file "
          f"{os.path.getsize(pb)} bytes; {len(index['layers'])} "
          f"instructions; scopes {index['scopes_found']}")
    shutil.rmtree(work, ignore_errors=True)
    if not trace.devices:
        print("no device plane in the trace (not a TPU): nothing to "
              "account")
        return 0
    lo, hi = xplane.window(trace)
    ordinal, ops = sorted(trace.devices.items())[0]
    acc = layer_account.own_seconds_by_layer(
        ops, lo, hi, index, trace.modules.get(ordinal) or None)
    busy = sum(e - s for s, e in xplane.busy(ops, lo, hi))
    print(json.dumps({"account_s": acc, "busy_s": busy,
                      "runs": len(xplane.runs_of(
                          trace.modules.get(ordinal, []),
                          index["module"], lo, hi))}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
