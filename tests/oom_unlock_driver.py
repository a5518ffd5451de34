"""The OOM-unlock proof (ISSUE 18 headline), in its OWN process.

A model whose compiled peak REFUSES every 2-D plan still trains: the
preflight backfills the shortlist from the 3-D lattice and a pp>1
plan wins, with the refusal, the stage cut and the bubble all in the
decision record. ``compiled_step_memory`` is stubbed so every 2-axis
plan "needs" 10GB while stage-sharding over the pipe axis fits the
1GB budget — the scenario the 2-D space structurally cannot express.

Run in a subprocess by tests/test_tune.py: an in-process multi-mesh
search is exactly the workload that intermittently hard-crashes this
XLA:CPU toolchain (see tests/mesh_search_driver.py), and a toolchain
abort is a process kill pytest's try/except can never catch —
isolation turns it into a retryable driver failure instead of a dead
test session.

Run: python tests/oom_unlock_driver.py
"""

from __future__ import annotations

import json
import os
import sys
import tempfile

os.environ["JAX_PLATFORMS"] = "cpu"
if "--xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count"
                                 "=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main():
    import jax.numpy as jnp
    import numpy as np

    import parallax_tpu as parallax
    from parallax_tpu.core import mesh as mesh_lib
    from parallax_tpu.models import long_context as lc
    from parallax_tpu.obs import memwatch as memwatch_lib

    def fake_compiled_step_memory(engine):
        # every 2-axis plan "needs" 10GB; stage-sharding the blocks
        # over the pipe axis fits the 1GB budget
        flat = mesh_lib.AXIS_PIPE not in engine.mesh.axis_names
        return {"peak_bytes": int(10e9) if flat else 1000,
                "basis": "test"}

    memwatch_lib.compiled_step_memory = fake_compiled_step_memory

    cfg = lc.tiny_config(parallelism="pipeline", num_layers=4,
                         num_microbatches=2,
                         pipeline_schedule="gpipe",
                         compute_dtype=jnp.float32)
    flight_dir = tempfile.mkdtemp(prefix="oom_unlock_")
    sess, *_ = parallax.parallel_run(
        lc.build_model(cfg),
        parallax_config=parallax.Config(
            run_option="AR", search_partitions=False,
            eager_fetch=True, flight_dir=flight_dir,
            tune_config=parallax.TuneConfig(
                top_k=2, run_options=("AR",), max_pp=4,
                trial_steps=2, trial_warmup=0, hbm_budget_gb=1.0)),
        num_partitions=1)
    try:
        feed = lc.make_batch(np.random.default_rng(3), 8, 16,
                             cfg.vocab_size)
        for _ in range(16):
            float(sess.run("loss", feed_dict=feed))
            if sess._search is None:
                break
        settled = sess._search is None
        s = sess.tune_summary()
        winner_scored = next(
            (pc for pc in s["scored"]
             if pc["plan"] == (s["winner"] or {}).get("plan")), {})
        art = [p for p in sess.flight.dump_paths
               if "tune_decision" in p]
        detail = (json.loads(open(art[0]).read())["detail"]
                  if art else {})
        print(json.dumps({
            "settled": settled,
            "pruned_oom": s["pruned_oom"],
            "refused": sorted(r["plan"]
                              for r in (s["oom_refusals"] or [])),
            "winner": s["winner"],
            "session_plan_pp": sess.plan.pp,
            "mesh_axes": list(sess.engine.mesh.axis_names),
            "winner_stage_cut":
                (winner_scored.get("pipeline") or {}).get("stage_cut"),
            "winner_wire_pp_s":
                (winner_scored.get("terms_ms") or {}).get("wire_pp_s"),
            "artifact_pruned_oom": detail.get("pruned_oom"),
            "artifact_winner_pp":
                (detail.get("winner") or {}).get("pp"),
        }))
    finally:
        sess.close()


if __name__ == "__main__":
    main()
