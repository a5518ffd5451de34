"""Two-process driver used by test_multihost.py (not a test itself).

Run as the master; the launcher re-executes this script on "both hosts"
(localhost + 127.0.0.1) over the local-exec path, each worker joining the
JAX coordination service with its own 4 emulated CPU devices.
"""

import os
import sys

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"

import numpy as np  # noqa: E402

import parallax_tpu as parallax  # noqa: E402
from parallax_tpu.models import simple  # noqa: E402


def main():
    out_path = sys.argv[1]
    model = simple.build_model(learning_rate=0.1)
    sess, num_workers, worker_id, num_replicas = parallax.parallel_run(
        model, resource_info="localhost\n127.0.0.1",
        parallax_config=parallax.Config(run_option="AR",
                                        search_partitions=False))
    rng = np.random.default_rng(worker_id)
    for _ in range(30):
        # each worker feeds ITS slice of the global batch
        batch = simple.make_batch(rng, 32)
        loss, step = sess.run(["loss", "global_step"], feed_dict=batch)
    with open(f"{out_path}.worker{worker_id}", "w") as f:
        f.write(f"workers={num_workers} replicas={num_replicas} "
                f"step={step} loss={loss:.6f} "
                f"w={float(sess.state.params['w'][0]):.4f} "
                f"b={float(sess.state.params['b'][0]):.4f}\n")
    sess.close()


if __name__ == "__main__":
    main()
