"""Decompose LM1B step wall time: device compute vs host overhead.

Measures, on the live backend:
  A. pure device step rate: device-resident batch, no per-step fetch
  B. + per-step device_put of the host batch
  C. + per-step blocking scalar fetch (the session's current behavior)
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np


def main():
    import jax
    import parallax_tpu as parallax
    from parallax_tpu.models import lm1b

    n = jax.device_count()
    platform = jax.devices()[0].platform
    cfg = (lm1b.LM1BConfig(num_partitions=n) if platform != "cpu"
           else lm1b.tiny_config(num_partitions=n))
    bs, T = (128 * n, 20) if platform != "cpu" else (16 * n, 8)
    sess, *_ = parallax.parallel_run(
        lm1b.build_model(cfg),
        parallax_config=parallax.Config(run_option="HYBRID",
                                        search_partitions=False))
    rng = np.random.default_rng(0)
    batches = [lm1b.make_batch(rng, bs, T, cfg.vocab_size)
               for _ in range(4)]
    for i in range(5):
        sess.run("loss", feed_dict=batches[i % 4])
    eng, state = sess.engine, sess.state
    dev_batches = [eng.shard_batch(b) for b in batches]
    jax.block_until_ready(state.params)
    N = 20

    # A: device-resident batches, fire-and-forget, block once
    t0 = time.perf_counter()
    for i in range(N):
        state, out = eng._step_jit(state, dev_batches[i % 4])
    jax.block_until_ready(state.params)
    a = (time.perf_counter() - t0) / N * 1e3

    # B: + device_put each step
    t0 = time.perf_counter()
    for i in range(N):
        state, out = eng._step_jit(state, eng.shard_batch(batches[i % 4]))
    jax.block_until_ready(state.params)
    b = (time.perf_counter() - t0) / N * 1e3

    # C: + blocking scalar fetch each step
    t0 = time.perf_counter()
    for i in range(N):
        state, out = eng._step_jit(state, eng.shard_batch(batches[i % 4]))
        float(np.asarray(out["words"]))
    jax.block_until_ready(state.params)
    c = (time.perf_counter() - t0) / N * 1e3

    # D: device_put cost alone
    t0 = time.perf_counter()
    for i in range(N):
        jax.block_until_ready(eng.shard_batch(batches[i % 4]))
    d = (time.perf_counter() - t0) / N * 1e3

    print(f"platform={platform}")
    print(f"A pure device step:        {a:7.1f} ms")
    print(f"B + device_put per step:   {b:7.1f} ms")
    print(f"C + blocking fetch:        {c:7.1f} ms")
    print(f"D device_put alone:        {d:7.1f} ms")
    sess.close()


if __name__ == "__main__":
    main()
