"""Two-process zig-zag ring-attention driver used by test_multihost.py.

Each worker feeds the natural-order process-local slice of one shared
global batch (same seed everywhere); the zig-zag placement happens
in-graph (models/long_context.py), so the 2-process trajectory must match
a single-host run on the same global batch exactly.
"""

import os
import sys

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"

import numpy as np  # noqa: E402

import parallax_tpu as parallax  # noqa: E402
from parallax_tpu.models import long_context as lc  # noqa: E402

STEPS, B, T = 5, 2, 32


def main():
    out_path = sys.argv[1]
    cfg = lc.tiny_config(max_len=T)
    cfg.zigzag = True
    model = lc.build_model(cfg)
    sess, num_workers, worker_id, _ = parallax.parallel_run(
        model, resource_info="localhost\n127.0.0.1",
        parallax_config=parallax.Config(run_option="HYBRID",
                                        search_partitions=False),
        num_partitions=8)
    assert num_workers == 2
    losses = []
    for step in range(STEPS):
        batch = lc.make_batch(np.random.default_rng(step), B, T,
                              cfg.vocab_size)
        # natural-order ids; this worker feeds its half of the sequence
        half = T // num_workers
        local = batch["ids"][:, worker_id * half:(worker_id + 1) * half]
        loss = sess.run("loss", feed_dict={"ids": local})
        losses.append(float(loss))
    with open(f"{out_path}.worker{worker_id}", "w") as f:
        f.write(" ".join(f"{x:.6f}" for x in losses) + "\n")
    sess.close()


if __name__ == "__main__":
    main()
