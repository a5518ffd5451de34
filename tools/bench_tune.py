"""One MeshSearch decision as a standalone JSON line — the bench
``tune`` block (ISSUE 10).

Run in its OWN process by bench.py: an in-process multi-mesh search is
exactly the workload that intermittently hard-crashes this XLA:CPU
toolchain (see tests/mesh_search_driver.py), and a toolchain abort is
a process kill the worker's try/except can never catch — isolation
makes a crash cost the round its tune block, never the whole BENCH
artifact with the already-measured headline in it.

Always pins itself to the 8-virtual-device CPU platform: on a TPU
round the parent worker holds the chip claim (a second process cannot
initialize it), and a platform-constant block keeps the regression
gate's cross-round ``tune.*`` comparisons apples-to-apples. The
platform is stamped into the block so a reader never mistakes the
predicted-over-measured ratio for a TPU number.

Run: python tools/bench_tune.py
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


def measure(top_k: int = 3, trial_steps: int = 6,
            trial_warmup: int = 2) -> dict:
    """One tuned smoke-flagship session driven to convergence; returns
    the bench block (tune summary + cache counters, per-plan score
    table dropped — the flight provider keeps it)."""
    import jax
    import numpy as np

    import parallax_tpu as parallax
    from parallax_tpu.models import lm1b

    n_chips = jax.device_count()
    cfg = lm1b.tiny_config(num_partitions=n_chips,
                           num_samples=16 * n_chips)
    sess, *_ = parallax.parallel_run(
        lm1b.build_model(cfg),
        parallax_config=parallax.Config(
            run_option="HYBRID", search_partitions=False,
            tune_config=parallax.TuneConfig(
                top_k=top_k, trial_steps=trial_steps,
                trial_warmup=trial_warmup)))
    try:
        rng = np.random.default_rng(0)
        batch = lm1b.make_batch(rng, 4 * n_chips, 8, cfg.vocab_size)
        for _ in range(top_k * trial_steps + 8):
            sess.run("loss", feed_dict=batch)
            if sess._search is None:
                break
        block = sess.tune_summary()
        if block is None:
            return {"error": "search did not settle"}
        block = dict(block)
        block.pop("scored", None)
        block["engine_cache"] = sess.compile_stats()["engine_cache"]
        w = block.get("winner") or {}
        block["predicted_over_measured"] = \
            w.get("predicted_over_measured")
        block["platform"] = jax.devices()[0].platform
        return block
    finally:
        sess.close()


def measure_pp_trial(top_k: int = 3, trial_steps: int = 4,
                     trial_warmup: int = 1) -> dict:
    """The pipeline-axis companion decision (ISSUE 18): the same
    tuned-session machinery pointed at the tiny pipeline LM with the
    pp dimension open. ``max_tp=1`` keeps the pool to the replicated
    column, so beyond the one 2-D plan every candidate is a genuine
    ``pp > 1`` plan and the shortlist must trial at least one. The
    gated number is a pp>1 trial row's predicted-over-measured —
    CPU-relative in absolute terms; cross-round DRIFT is the signal
    (the bubble+transfer pricing and the measured schedule coming
    apart)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import parallax_tpu as parallax
    from parallax_tpu.models import long_context as lc

    n_chips = jax.device_count()
    cfg = lc.tiny_config(parallelism="pipeline", num_layers=8,
                         num_microbatches=4,
                         compute_dtype=jnp.float32)
    sess, *_ = parallax.parallel_run(
        lc.build_model(cfg),
        parallax_config=parallax.Config(
            run_option="HYBRID", search_partitions=False,
            tune_config=parallax.TuneConfig(
                top_k=top_k, trial_steps=trial_steps,
                trial_warmup=trial_warmup,
                run_options=("HYBRID",), max_tp=1,
                max_pp=n_chips)),
        num_partitions=1)
    try:
        batch = lc.make_batch(np.random.default_rng(0), 32, 16,
                              cfg.vocab_size)
        for _ in range(top_k * trial_steps + 8):
            sess.run("loss", feed_dict=batch)
            if sess._search is None:
                break
        block = sess.tune_summary()
        if block is None:
            return {"error": "pp search did not settle"}
        rows = [t for t in (block.get("trials") or [])
                if "xpp" in t["plan"] and t.get("measured_ms")
                and t.get("predicted_ms")]
        if not rows:
            return {"error": "no pp > 1 plan reached a measured trial"}
        row = rows[0]
        w = block.get("winner") or {}
        return {
            "plan": row["plan"],
            "predicted_ms": row["predicted_ms"],
            "measured_ms": row["measured_ms"],
            "predicted_over_measured": round(
                row["predicted_ms"] / row["measured_ms"], 6),
            "winner_plan": w.get("plan"),
            "winner_pp": w.get("pp"),
            "winner_bubble_fraction": w.get("bubble_fraction"),
        }
    finally:
        sess.close()


def main():
    block = measure()
    try:
        block["pp_trial"] = measure_pp_trial()
    except Exception as exc:  # a pp failure costs only the sub-block
        block["pp_trial"] = {"error": repr(exc)}
    print(json.dumps(block))


if __name__ == "__main__":
    main()
