"""Per-device HBM accounting for the flagship configuration.

Three layers of evidence (committed under perf/ per ROADMAP item 12;
the third added by ISSUE 13):

1. **State bytes, exact, from the sharding plan** (abstract eval — no
   allocation): params / optimizer state / slice-adagrad accumulators,
   per device, split replicated vs sharded. This is where the hybrid
   design pays off — the 793k-vocab tables and their accumulators are
   row-sharded while the LSTM stack is replicated.
2. **Compiled-step memory analysis** (XLA `memory_analysis()` on the
   jitted training step, through the shared
   ``obs/memwatch.compiled_memory`` helper — one owner for the field
   set and the derived peak): activation/temp footprint the compiler
   actually schedules, argument/output aliasing included. Compiling the
   full flagship on the CPU emulator is expensive, so this layer runs
   on a scaled config by default (`--compile_scale`) and on the real
   one with `--compile_scale 1`.
3. **Runtime-measured live peak** (``obs/memwatch.MemWatch`` sampling
   ``device_memory_stats`` across real executed steps): what the
   allocator actually held, next to what the plan says it should and
   what the compiler scheduled. Honest on the CPU rig: XLA:CPU
   reports no memory stats, so this layer records ``unavailable``
   there instead of a fabricated number — it goes live on TPU capture.

Run: python tools/memory_report.py [--out perf/MEMORY_r04.json]
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


def _per_device_bytes(tree, mesh):
    """(replicated_bytes, sharded_bytes) one device holds for a pytree
    of arrays/ShapeDtypeStructs with known shardings."""
    import jax
    import numpy as np

    n = mesh.devices.size
    repl = sharded = 0
    for leaf in jax.tree.leaves(tree):
        if not hasattr(leaf, "shape"):
            continue
        total = int(np.prod(leaf.shape or (1,))) * leaf.dtype.itemsize
        sharding = getattr(leaf, "sharding", None)
        if sharding is None or sharding.is_fully_replicated:
            repl += total
        else:
            shard_elems = int(np.prod(
                sharding.shard_shape(leaf.shape) or (1,)))
            sharded += shard_elems * leaf.dtype.itemsize
    return repl, sharded


def state_accounting(n_chips=8, batch_per_chip=128, num_steps=20,
                     table_dtype="float32"):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from parallax_tpu.common.config import ParallaxConfig
    from parallax_tpu.core import engine as engine_lib, mesh as mesh_lib
    from parallax_tpu.models import lm1b

    mesh = mesh_lib.build_mesh(jax.devices()[:n_chips],
                               num_partitions=n_chips)
    cfg = lm1b.LM1BConfig(num_partitions=n_chips,
                          sparse_grad_mode="slices",
                          table_dtype=jnp.dtype(table_dtype))
    model = lm1b.build_model(cfg)
    batch = lm1b.make_batch(np.random.default_rng(0),
                            batch_per_chip * n_chips, num_steps,
                            cfg.vocab_size)
    config = ParallaxConfig(run_option="HYBRID", search_partitions=False,
                            sparse_grad_mode="slices")
    eng = engine_lib.Engine(model, mesh, config, batch)
    # eval_shape drops the plan's shardings; compiling init (no
    # execution, no allocation) exposes them via output_shardings
    shapes = jax.eval_shape(eng._init_jit, 0)
    shardings = eng._init_jit.lower(0).compile().output_shardings
    state = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                           sharding=sh),
        shapes, shardings)

    out = {}
    for name, tree in (("params", state.params),
                       ("opt_state", state.opt_state),
                       ("slice_state", state.slice_state)):
        repl, shard = _per_device_bytes(tree, mesh)
        out[name] = {"replicated_bytes": repl, "sharded_bytes": shard,
                     "per_device_bytes": repl + shard}
    parts = list(out.values())
    out["total_per_device_bytes"] = sum(
        v["per_device_bytes"] for v in parts)
    # what a pure-replication design (the reference's MPI mode) would
    # hold per device: every sharded plane times the shard count
    n = mesh.devices.size
    out["replicated_design_per_device_bytes"] = sum(
        v["replicated_bytes"] + v["sharded_bytes"] * n for v in parts)
    return out


def compiled_accounting(n_chips=8, scale=8):
    """memory_analysis() of the compiled hybrid step on a 1/scale-vocab
    config (the full flagship compiles too slowly on the CPU emulator
    for routine runs)."""
    import jax
    import numpy as np

    from parallax_tpu.common.config import ParallaxConfig
    from parallax_tpu.core import engine as engine_lib, mesh as mesh_lib
    from parallax_tpu.models import lm1b

    mesh = mesh_lib.build_mesh(jax.devices()[:n_chips],
                               num_partitions=n_chips)
    cfg = lm1b.LM1BConfig(vocab_size=793470 // scale,
                          num_samples=8192 // scale,
                          num_partitions=n_chips,
                          sparse_grad_mode="slices")
    model = lm1b.build_model(cfg)
    batch = lm1b.make_batch(np.random.default_rng(0), 128 * n_chips,
                            20, cfg.vocab_size)
    config = ParallaxConfig(run_option="HYBRID", search_partitions=False,
                            sparse_grad_mode="slices")
    eng = engine_lib.Engine(model, mesh, config, batch)
    state = jax.eval_shape(eng._init_jit, 0)
    placed = eng.shard_batch(batch)
    abstract_batch = {
        k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=v.sharding)
        for k, v in placed.items()}
    with eng.mesh:
        compiled = eng._step_jit.lower(state, abstract_batch).compile()
    # the shared field set + derived peak (obs/memwatch.py) — the same
    # numbers the tuner's OOM preflight judges
    from parallax_tpu.obs import memwatch
    mem = memwatch.compiled_memory(compiled)
    if mem is None:
        raise RuntimeError("memory_analysis unavailable on this "
                           "backend")
    return {"vocab_scale": scale, **mem}


def runtime_accounting(n_chips=8, scale=8, steps=5):
    """Third evidence layer: live allocator peak across real executed
    steps of the scaled config (obs/memwatch ring over
    device_memory_stats). ``unavailable`` — honestly — on backends
    without memory stats (XLA:CPU)."""
    import jax
    import numpy as np

    from parallax_tpu.common.config import ParallaxConfig
    from parallax_tpu.core import engine as engine_lib, mesh as mesh_lib
    from parallax_tpu.models import lm1b
    from parallax_tpu.obs.memwatch import MemWatch

    mesh = mesh_lib.build_mesh(jax.devices()[:n_chips],
                               num_partitions=n_chips)
    cfg = lm1b.LM1BConfig(vocab_size=793470 // scale,
                          num_samples=8192 // scale,
                          num_partitions=n_chips,
                          sparse_grad_mode="slices")
    model = lm1b.build_model(cfg)
    batch = lm1b.make_batch(np.random.default_rng(0), 128 * n_chips,
                            20, cfg.vocab_size)
    config = ParallaxConfig(run_option="HYBRID", search_partitions=False,
                            sparse_grad_mode="slices")
    eng = engine_lib.Engine(model, mesh, config, batch)
    state = eng.init_state(0)
    mw = MemWatch()
    for step in range(steps):
        state, _ = eng.step(state, batch)
        jax.block_until_ready(state.params)
        mw.sample(step)
    peak = mw.live_peak_bytes()
    return {
        "vocab_scale": scale, "steps": steps,
        "live_peak_bytes": peak,
        "note": (None if peak else
                 "backend reports no device memory stats "
                 "(XLA:CPU); goes live on TPU capture"),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None)
    ap.add_argument("--n_chips", type=int, default=8)
    ap.add_argument("--compile_scale", type=int, default=8)
    args = ap.parse_args()
    result = {
        "state_fp32_tables": state_accounting(args.n_chips),
        "state_bf16_tables": state_accounting(args.n_chips,
                                              table_dtype="bfloat16"),
    }
    try:
        result["compiled_step"] = compiled_accounting(
            args.n_chips, args.compile_scale)
    except Exception as e:  # memory_analysis availability varies
        result["compiled_step"] = {"error": str(e)[:300]}
    try:
        result["measured_runtime"] = runtime_accounting(
            args.n_chips, args.compile_scale)
    except Exception as e:
        result["measured_runtime"] = {"error": str(e)[:300]}
    line = json.dumps(result)
    print(line)
    if args.out:
        with open(args.out, "w") as f:
            f.write(line + "\n")


if __name__ == "__main__":
    main()
