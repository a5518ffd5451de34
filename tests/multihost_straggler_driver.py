"""Two-process straggler-detection driver used by test_multihost.py
(not a test itself): worker 1 is an INJECTED straggler — it sleeps
STRAGGLE_S before every dispatch, emulating a host stalled on input /
a sick daemon — and the cross-process aggregation
(``sess.aggregate_host_steps``, obs/aggregate.py) must NAME it in the
artifact every process receives.

The signal is the host-side dispatch wall (obs/timeline.py): under the
async pipeline each host dispatches at its own host speed (lazy
fetches — the device-side collective barrier doesn't equalize the
dispatch timelines), so the delayed host's wall is ~STRAGGLE_S higher
than its peers'. Worker 0 also writes a flight dump whose
``host_report`` section carries the same named-straggler report.
"""

import json
import os
import sys
import time

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"

import numpy as np  # noqa: E402

import parallax_tpu as parallax  # noqa: E402
from parallax_tpu.models import simple  # noqa: E402

WARMUP = 4            # un-straggled steps absorbing the compile
STEPS = 24
STRAGGLE_S = 0.03     # worker 1's injected per-step host delay
FACTOR = 1.25


def main():
    out_path = sys.argv[1]
    flight_dir = sys.argv[2]
    model = simple.build_model(learning_rate=0.1)
    # flight_steps == STEPS: the timeline ring holds exactly the
    # straggled window, so the compile-dominated warmup rows (equal on
    # every host) can't dilute the aggregated means
    sess, num_workers, worker_id, _ = parallax.parallel_run(
        model, resource_info="localhost\n127.0.0.1",
        parallax_config=parallax.Config(run_option="AR",
                                        search_partitions=False,
                                        flight_dir=flight_dir,
                                        flight_steps=STEPS))
    rng = np.random.default_rng(worker_id)
    handles = []
    for i in range(WARMUP + STEPS):
        if worker_id == 1 and i >= WARMUP:
            time.sleep(STRAGGLE_S)  # the injected host-side straggle
        # lazy fetch: dispatch must not block on the device barrier,
        # or every host's wall would equalize and hide the straggler
        handles.append(sess.run("loss", feed_dict=simple.make_batch(
            rng, 32)))
    loss = float(handles[-1])  # drain

    # COLLECTIVE: both processes call; both receive the named report
    report = sess.aggregate_host_steps(factor=FACTOR)
    dump_path = sess.dump_flight(
        os.path.join(flight_dir, f"flight_worker{worker_id}.json"),
        reason="straggler_driver")
    with open(f"{out_path}.worker{worker_id}", "w") as f:
        json.dump({"worker_id": worker_id, "num_workers": num_workers,
                   "loss": loss, "report": report,
                   "flight_path": dump_path}, f)
    sess.close()


if __name__ == "__main__":
    main()
