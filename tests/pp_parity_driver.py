"""Loss-parity proof for a tuner-emitted pp>1 plan, in its OWN process.

Acceptance (ISSUE 18): a pipeline plan trains to the SAME losses as
the pp=1 baseline (4-decimal tolerance). State is initialized on the
pp=1 mesh and resharded onto the pipeline plan (the session's replan
path): on this toolchain, sharding-constrained multi-call RNG init is
sharding-dependent for stacked layer params, so init-then-reshard is
the value-preserving route — the same one the live tuner takes when
it switches plans.

Run in a subprocess by tests/test_pipeline.py: in-process multi-mesh
engine builds + steps are exactly the workload that intermittently
hard-crashes this XLA:CPU toolchain (see tests/mesh_search_driver.py)
— a toolchain abort is a process kill pytest's try/except can never
catch, so isolation turns it into a retryable driver failure instead
of a dead test session.

One process covers BOTH schedules against one shared baseline: at
pp=1 both GPipe and 1F1B reduce to the same sequential microbatch
accumulation, so the baseline is schedule-independent (asserted) and
only needs building once.

Run: python tests/pp_parity_driver.py [schedule ...]
"""

from __future__ import annotations

import json
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
if "--xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count"
                                 "=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main():
    schedules = sys.argv[1:] or ["gpipe", "1f1b"]
    import jax.numpy as jnp
    import numpy as np

    import parallax_tpu as parallax
    from parallax_tpu.core import mesh as mesh_lib
    from parallax_tpu.models import long_context as lc
    from parallax_tpu.tune.costmodel import Plan

    rng = np.random.default_rng(7)
    batches = [lc.make_batch(rng, 8, 16, 512) for _ in range(3)]

    def run_plan(schedule, plan):
        cfg = lc.tiny_config(num_layers=4, max_len=16,
                             compute_dtype=jnp.float32)
        cfg.parallelism = "pipeline"
        cfg.num_microbatches = 2
        cfg.pipeline_schedule = schedule
        sess, *_ = parallax.parallel_run(
            lc.build_model(cfg),
            parallax_config=parallax.Config(run_option="HYBRID",
                                            search_partitions=False),
            num_partitions=1)
        try:
            sess.prepare(batches[0])    # init on the pp=1 mesh
            if plan is not None:
                sess._build_engine(batches[0], plan)  # reshard, no re-init
                assert mesh_lib.AXIS_PIPE in sess.engine.mesh.axis_names
            return [float(sess.run("loss", feed_dict=b))
                    for b in batches]
        finally:
            sess.close()

    base = run_plan(schedules[0], None)
    pp2 = {s: run_plan(s, Plan(dp=4, tp=1, run_option="HYBRID", pp=2,
                               microbatches=2))
           for s in schedules}
    print(json.dumps({"schedules": schedules, "base": base,
                      "pp2": pp2}))


if __name__ == "__main__":
    main()
