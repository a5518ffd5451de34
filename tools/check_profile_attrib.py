"""Guard: measured per-op attribution must explain the device step wall.

ISSUE 13 acceptance, the ``check_serve_slo``/``check_train_faults``
pattern: drive a real profiled window end to end on the tier-1 CPU
backend and assert the plan observatory's core contracts —

  1. the per-op attribution accounts for >= 90% of the measured
     device step wall, with the residual reported explicitly (never
     hidden inside a category);
  2. the taxonomy is live: collectives are seen on the multi-device
     mesh, category shares sum to ~1, and the dense-vs-sparse split
     attributes real self-time to the sparse (row-sharded table)
     path on an embedding-bearing model;
  3. the calibration loop closes: per-term predicted/measured ratios
     derive from the same window, round-trip through the persisted
     calibration file (tune/calibrate.py), and survive reload;
  4. memwatch's compiled-memory account resolves off the warmed
     executables.

Run directly::

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python tools/check_profile_attrib.py

or via tier-1 (tests/test_profile.py subprocess guard).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile

if "--xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_"
                                 "count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

V, D, BATCH = 8192, 32, 256


def _model():
    import jax
    import jax.numpy as jnp
    import optax

    import parallax_tpu as parallax
    from parallax_tpu.ops import embedding as emb_ops

    def init_fn(rng):
        return {"emb": jax.random.normal(rng, (V, D)) * 0.1,
                "w": jnp.eye(D) * 0.1}

    def loss_fn(params, batch):
        rows = emb_ops.embedding_lookup(params["emb"], batch["ids"])
        return jnp.mean((rows @ params["w"]) ** 2)

    return parallax.Model(init_fn, loss_fn,
                          optimizer=optax.sgd(0.1))


def measure(steps: int = 6, warm: int = 4) -> dict:
    """One profiled window end to end; returns the JSON-ready report."""
    import jax
    import numpy as np

    import parallax_tpu as parallax
    from parallax_tpu.obs import memwatch
    from parallax_tpu.tune import calibrate, costmodel

    sess, *_ = parallax.parallel_run(
        _model(),
        parallax_config=parallax.Config(
            run_option="HYBRID", search_partitions=False,
            eager_fetch=True))
    try:
        rng = np.random.default_rng(0)
        feed = {"ids": rng.integers(0, V, (BATCH,)).astype(np.int32)}
        sess.prepare(feed)
        # warmup BEFORE profiling: the AOT executable is what the
        # window's steps dispatch, so the HLO index used for
        # layer/sparse mapping is the exact executed module
        sess.warmup(batch_sizes=[BATCH])
        for _ in range(warm):
            float(sess.run("loss", feed_dict=feed))
        outdir = sess.profile_steps(steps)
        for _ in range(steps):
            float(sess.run("loss", feed_dict=feed))
        attrib = sess.profile_summary()
        if not attrib or attrib.get("error"):
            raise RuntimeError(f"attribution failed: {attrib}")

        shares = {cat: row["share"]
                  for cat, row in attrib["by_category"].items()}

        # calibration off the same window: the cost model's per-term
        # prediction for the live plan vs the measured aggregates
        inputs = costmodel.inputs_from_engine(sess.engine)
        pc = costmodel.predict(sess.plan, inputs)
        predicted = calibrate.predicted_terms_from_cost(pc.terms)
        measured = calibrate.measured_terms_from_attribution(
            attrib, jax.device_count())
        record = calibrate.build_record(
            predicted, measured, basis="cpu-nominal",
            meta={"tool": "check_profile_attrib",
                  "plan": sess.plan.describe()})
        with tempfile.TemporaryDirectory() as td:
            path = os.path.join(td, "calibration.json")
            calibrate.save(path, record)
            reloaded = calibrate.load(path)
            roundtrip_ok = (reloaded is not None
                            and calibrate.ratios(reloaded)
                            == calibrate.ratios(record))

        compiled = memwatch.compiled_step_memory(sess.engine)
        ratios = calibrate.ratios(record) or {}
        return {
            "attribution_coverage": attrib["coverage"],
            "residual_ms": attrib["residual_ms"],
            "attributed_ms": attrib["attributed_ms"],
            "wall_ms": attrib["wall_ms"],
            "window_span_ms": attrib["window_span_ms"],
            "inter_step_ms": attrib["inter_step_ms"],
            "step_wall_ms": attrib["step_wall_ms"],
            "steps": attrib["steps"],
            "events": attrib["events"],
            "track_basis": attrib["track_basis"],
            "shares": shares,
            "collectives": attrib["collectives"],
            "top_ops": attrib["top_ops"][:5],
            "dense_sparse": attrib["dense_sparse"],
            "calibration": {
                "on_chip_predicted_over_measured":
                    ratios.get("on_chip"),
                "wire_predicted_over_measured": ratios.get("wire"),
                "terms": record["terms"],
            },
            "calibration_roundtrip_ok": roundtrip_ok,
            "memwatch": {
                "compiled_peak_bytes": (compiled or {}).get(
                    "peak_bytes"),
                "compiled_basis": (compiled or {}).get("basis"),
            },
            "capture_dir": outdir,
        }
    finally:
        sess.close()


def check(res: dict, min_coverage: float = 0.90) -> list:
    """Violation list (empty = pass) over one measure() report."""
    v = []
    cov = res.get("attribution_coverage")
    if not isinstance(cov, (int, float)) or cov < min_coverage:
        v.append(f"attribution coverage {cov!r} < {min_coverage} of "
                 f"the measured device step wall")
    if "residual_ms" not in res \
            or not isinstance(res["residual_ms"], (int, float)) \
            or res["residual_ms"] < 0:
        v.append("residual_ms missing/negative — the unattributed "
                 "share must be reported explicitly")
    shares = res.get("shares") or {}
    total = sum(shares.values())
    if abs(total - 1.0) > 0.02:
        v.append(f"category shares sum to {total:.4f}, not ~1")
    if shares.get("collective", 0) <= 0:
        v.append("no collective self-time attributed on a "
                 "multi-device mesh")
    ds = res.get("dense_sparse") or {}
    if ds.get("sparse_self_ms", 0) <= 0:
        v.append("dense/sparse split attributed no time to the "
                 "sparse table path on an embedding model")
    cal = res.get("calibration") or {}
    for term in ("on_chip_predicted_over_measured",
                 "wire_predicted_over_measured"):
        r = cal.get(term)
        if not isinstance(r, (int, float)) or r <= 0:
            v.append(f"calibration {term} is {r!r}, expected > 0")
    if not res.get("calibration_roundtrip_ok"):
        v.append("calibration file round-trip failed")
    if not res.get("memwatch", {}).get("compiled_peak_bytes"):
        v.append("memwatch compiled-memory account did not resolve "
                 "off the warmed executables")
    return v


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--steps", type=int, default=6)
    ap.add_argument("--min-coverage", type=float, default=0.90)
    args = ap.parse_args(argv)
    res = measure(steps=args.steps)
    violations = check(res, args.min_coverage)
    res["ok"] = not violations
    res["violations"] = violations or None
    print(json.dumps(res))
    return 0 if res["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
