"""Flagship wire-bytes accounting: sparse path vs dense all-reduce.

The BASELINE.json north-star secondary metric is "sparse-grad bytes on
wire" — the reference's PS win is shipping only the touched (ids, rows)
of the 793k-vocab embedding/softmax tables instead of dense [V, D]
gradients (reference: graph_transform_lib.py:1041-1211). The accounting
is trace-time (ops/embedding.py records per-lookup wire terms while the
step traces), so the REAL flagship config can be measured anywhere: this
script abstractly evaluates the full hybrid training step (no parameter
allocation, no execution) on an 8-virtual-device CPU mesh and prints the
accounting as one JSON line.

Run: python tools/wire_bytes_report.py [--out WIRE_BYTES.json]
"""

from __future__ import annotations

import argparse
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


def flagship_accounting(n_chips: int = 8, batch_per_chip: int = 128,
                        num_steps: int = 20, table_dtype: str = "float32",
                        dedup_capacity=None):
    """Build the flagship engine (793,470-vocab LM1B, HYBRID,
    slices mode) and return its wire-bytes accounting from an abstract
    trace of one training step.

    ``table_dtype='bfloat16'`` halves every row plane on the wire (the
    accounting models the element size exactly — ops/embedding.py);
    ``dedup_capacity`` declares the guarded per-device unique-id slot
    count (PSConfig.dedup_capacity) — the report then also verifies the
    declared capacity against the REAL distinct-id counts of the seeded
    batch so the committed number is never the optimistic lower bound of
    an overflowing configuration."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from parallax_tpu.common.config import (CommunicationConfig,
                                            ParallaxConfig, PSConfig)
    from parallax_tpu.core import engine as engine_lib, mesh as mesh_lib
    from parallax_tpu.models import lm1b

    devices = jax.devices()[:n_chips]
    mesh = mesh_lib.build_mesh(devices, num_partitions=n_chips)
    cfg = lm1b.LM1BConfig(num_partitions=n_chips,
                          sparse_grad_mode="slices",
                          table_dtype=jnp.dtype(table_dtype))
    model = lm1b.build_model(cfg)
    batch = lm1b.make_batch(np.random.default_rng(0),
                            batch_per_chip * n_chips, num_steps,
                            cfg.vocab_size)
    overflow_free = None

    def max_distinct(arr):
        return max(len(np.unique(c))
                   for c in np.split(arr.reshape(-1), n_chips))

    if dedup_capacity == "auto":
        # Per-table capacities from the REAL distinct-id profile of the
        # seeded batch (+ two 128-blocks of margin), per lookup: the emb
        # table gathers input ids (Zipf, heavy duplication); the softmax
        # tables gather labels + a 1/n_chips slice of the log-uniform
        # candidates (distinct count upper-bounded by labels-distinct +
        # slice length). The runtime lax.cond guard keeps any
        # out-of-profile step exact regardless.
        def padded(b):
            return (b // 128 + 2) * 128

        emb_cap = padded(max_distinct(batch["x"]))
        sm_cap = padded(max_distinct(batch["y"])
                        + cfg.num_samples // n_chips)
        # path keys: emb and softmax_w share a shape in the flagship
        dedup_capacity = {"emb": emb_cap, "softmax_w": sm_cap,
                          "softmax_b": sm_cap}
        overflow_free = True  # by construction, for the measured batch
    elif isinstance(dedup_capacity, dict):
        # round-trip of an 'auto'-style dict: check each declared table
        # against its own lookup's distinct-id bound
        emb_bound = max_distinct(batch["x"])
        sm_bound = (max_distinct(batch["y"])
                    + cfg.num_samples // n_chips)
        bounds = {"emb": emb_bound, "softmax_w": sm_bound,
                  "softmax_b": sm_bound}
        overflow_free = all(
            bounds.get(k, 0) <= v for k, v in dedup_capacity.items())
    elif dedup_capacity is not None:
        bound = max(max_distinct(batch["x"]),
                    max_distinct(batch["y"])
                    + cfg.num_samples // n_chips)
        overflow_free = bool(bound <= dedup_capacity)
    config = ParallaxConfig(
        run_option="HYBRID", search_partitions=False,
        sparse_grad_mode="slices",
        communication_config=CommunicationConfig(
            ps_config=PSConfig(dedup_capacity=dedup_capacity)))
    eng = engine_lib.Engine(model, mesh, config, batch)

    # Abstract evaluation: traces the step (filling the per-lookup wire
    # records) without allocating the 793k-vocab tables or running math.
    abstract_state = jax.eval_shape(eng._init_jit, 0)
    abstract_batch = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                      for k, v in eng.shard_batch(batch).items()}
    with eng.mesh:
        jax.eval_shape(eng._step_jit, abstract_state, abstract_batch)
    wire = eng.sparse_wire_bytes_per_step()
    # Derived ratios come from tune/costmodel.py — the ONE owner of the
    # wire-byte math (ISSUE 10; this script used to duplicate it).
    # The reference baseline: TF ships fp32 dense gradients whatever
    # the table dtype (BASELINE.md). The engine's dense alternative
    # counts the tables in their OWN dtype; all lm1b tables share
    # table_dtype, so the fp32 reference is a pure element-size
    # rescale of it.
    from parallax_tpu.tune import costmodel
    summary = costmodel.wire_summary(
        wire, table_elem_bytes=jnp.dtype(cfg.table_dtype).itemsize)
    return {
        "config": {
            "model": "lm1b", "vocab_size": cfg.vocab_size,
            "emb_dim": cfg.emb_dim, "proj_dim": cfg.proj_dim,
            "batch_size": batch_per_chip * n_chips,
            "num_steps": num_steps, "n_chips": n_chips,
            "run_option": "HYBRID", "sparse_grad_mode": "slices",
            "table_dtype": str(table_dtype),
            "dedup_capacity": dedup_capacity,
            "dedup_capacity_overflow_free": overflow_free,
        },
        **wire,
        "sparse_over_dense": summary["sparse_over_dense"],
        "dense_fp32_reference_bytes":
            summary["dense_fp32_reference_bytes"],
        "sparse_over_dense_fp32_ref":
            summary["sparse_over_dense_fp32_ref"],
    }


def pipeline_plan_section(pipeline: dict, num_devices: int = 8,
                          max_pp=None):
    """Per-plan inter-stage wire accounting for every pp > 1 plan the
    tuner can emit for a model with the given pipeline capability
    record (ISSUE 18 satellite). Pure math off the ONE wire owner
    (tune/costmodel.pipeline_wire_bytes / pipeline_bubble) — the same
    figures ``predict`` folds into ``wire_pp_s``, reported here as raw
    bytes so the report stays execution-free like the rest of the
    accounting."""
    from parallax_tpu.tune import costmodel
    from parallax_tpu.tune.search import emittable_plans

    act = float(pipeline.get("act_bytes") or 0.0)
    if not act:
        act = (float(pipeline.get("global_batch") or 0)
               * float(pipeline.get("model_dim") or 0)
               * float(pipeline.get("act_itemsize") or 4))
    schedule = str(pipeline.get("schedule") or "gpipe")
    rows = []
    for plan in emittable_plans(num_devices,
                                max_pp=max_pp or num_devices,
                                pipeline=pipeline):
        if plan.pp == 1:
            continue
        V = max(int(plan.virtual_stages), 1)
        M = int(plan.microbatches
                or pipeline.get("microbatches") or 1)
        w = costmodel.pipeline_wire_bytes(
            act, M, plan.pp, V, schedule=schedule,
            dp=plan.dp, tp=plan.tp)
        rows.append({
            "plan": plan.describe(),
            "pp": plan.pp,
            "schedule": schedule,
            "per_hop_bytes": w["per_hop_bytes"],
            "activation_bytes": w["activation_bytes"],
            "cotangent_bytes": w["cotangent_bytes"],
            "total_bytes": w["total_bytes"],
            "ticks": w["ticks"],
            "bubble_fraction": w["bubble_fraction"],
            "microbatches_scheduled": w["microbatches_scheduled"],
        })
    return {
        "act_bytes_per_boundary": act,
        "num_devices": num_devices,
        "plans": rows,
    }


def _demo_pipeline_record():
    """The pipeline capability record of the tiny pipeline LM that
    tests/mesh_search_driver.py's pp pool exercises — so --pipeline
    reports the same plan pool it measures."""
    from parallax_tpu.models import long_context as lc
    cfg = lc.tiny_config(parallelism="pipeline", num_layers=8,
                         num_microbatches=4)
    info = dict(lc.build_model(cfg).pipeline_info)
    # the model declares the schedule; the batch the drivers feed it
    # (B=32, T=16) sets the boundary activation: tokens x dim x 4B
    info["global_batch"] = 32
    info["act_bytes"] = 32 * 16 * cfg.model_dim * 4
    return info


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None,
                    help="also write the JSON to this path")
    ap.add_argument("--n_chips", type=int, default=8)
    ap.add_argument("--batch_per_chip", type=int, default=128)
    ap.add_argument("--table_dtype", default="float32",
                    choices=["float32", "bfloat16"])
    ap.add_argument("--dedup_capacity", default=None,
                    help="per-device unique-id slots: an int, or 'auto' "
                         "for per-table capacities from the measured "
                         "distinct-id profile")
    ap.add_argument("--pipeline", action="store_true",
                    help="append the per-plan pipeline wire section "
                         "(inter-stage bytes + bubble per pp>1 plan)")
    args = ap.parse_args()
    cap = args.dedup_capacity
    if cap is not None and cap != "auto":
        cap = int(cap)
    result = flagship_accounting(args.n_chips, args.batch_per_chip,
                                 table_dtype=args.table_dtype,
                                 dedup_capacity=cap)
    if args.pipeline:
        result["pipeline_plans"] = pipeline_plan_section(
            _demo_pipeline_record(), num_devices=args.n_chips)
    line = json.dumps(result)
    print(line)
    if args.out:
        with open(args.out, "w") as f:
            f.write(line + "\n")


if __name__ == "__main__":
    main()
