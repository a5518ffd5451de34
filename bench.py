"""Benchmark driver: prints ONE JSON line with the headline metric.

Headline (BASELINE.json): LM1B words/sec/chip. Trains the flagship LM1B
model (sampled softmax over the row-sharded 793k vocab) through
parallel_run and measures steady-state words/sec.

``vs_baseline`` compares against the naive dense path — full-softmax
LM1B, the "everything replicated, no sparse machinery" approach — at the
SAME (memory-limited) batch size, isolating the algorithmic win of the
sparse path from batch-size utilization. The headline value itself is
measured at the realistic batch size. Batch sizes scale with the chip
count (pure data parallelism).

One process: ``python bench.py`` IS the process that holds the device
(a chip belongs to one process at a time), and every child it starts is
pinned to the CPU. CPU-era harness: ``chip_smoke.py`` is the on-chip
bring-up proof, and ROADMAP A1 replaces this file with the cell table.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time


# Methodology version stamped into the JSON (VERDICT r4 weak item 4):
# cross-round vs_baseline comparisons are only valid within one version.
#   v1 (r1-r3): baseline = full-softmax at the HEADLINE batch size.
#   v2 (r4-r5): baseline = full-softmax at the largest COMMON batch both
#               paths fit (memory-limited), isolating the algorithmic win
#               from batch-size utilization; CPU smoke vocab 16k.
#   v3 (r6+):   headline methodology UNCHANGED from v2; the serve block
#               gains the continuous-decode concurrency sweep
#               (tokens/sec + TTFT per offered level, paged KV + chunked
#               prefill + speculative decode) and the decode block gains
#               the paged-vs-dense and speculative-vs-plain A/Bs
#               (ISSUE 6). The version bump exists so the regression
#               gate re-baselines the enlarged blocks; the same-build
#               A/B under v2 params attributes any headline move.
#               r7+: the serve block additionally carries a "fleet"
#               sub-block (chaos-harness failover/hot-swap latencies,
#               ISSUE 7) — a new sub-block, not a methodology change:
#               the regression gate SKIPS keys absent on either side,
#               so no version bump.
#               r8+: a top-level "ckpt" block (save/restore latency,
#               checkpoint bytes, async-save step-overhead A/B, train
#               chaos-harness outcome, ISSUE 9) — again a new block
#               with gate-side skip semantics, so no version bump.
#               r9+: a top-level "tune" block (auto-tuner v2 decision
#               record: plans enumerated/pruned/trialed, winner
#               predicted-vs-measured, search seconds, ISSUE 10) —
#               a new block with gate-side skip semantics, no bump.
#               r10+: the serve.continuous block gains trace-derived
#               keys (ttft_decomp phase shares, the per-percentile
#               dominant-cause report whose p99 keys are secondary-
#               gated, deadline_miss_budget_consumed) and serve.fleet
#               gains incident_correlated / ttft_decomp_max_rel_err
#               (ISSUE 12) — new keys, gate-side skip, no bump.
#               r14+: a top-level "lstm" block (ISSUE 14,
#               tools/bench_lstm.py: pallas-backward vs recompute-XLA
#               fwd+bwd A/B at op level and through one LM1B training
#               step, the interpret-tax witness, and the analytic
#               fwd+bwd HBM-bytes story at the flagship shape) — a
#               new block with gate-side skip semantics, no bump.
#               r16+: a top-level "attn" block (ISSUE 16,
#               tools/bench_paged_attn.py: fused paged-attention
#               kernel vs full-width einsum gather across pool
#               occupancies, the interpret-tax witness, and the
#               analytic live-pages-only vs gather HBM table at the
#               flagship decode shape) — a new block with gate-side
#               skip semantics, no bump.
#               r20+: a top-level "ops" block (ISSUE 20,
#               tools/check_goodput.py: run-lifetime goodput fraction
#               and badput breakdown from the chaos rig, plus the
#               journal-emit / alert-eval unit costs) — a new block
#               with gate-side skip semantics, no bump.
BENCH_VERSION = 3
BASELINE_BASIS = ("sampled-softmax vs full-softmax LM1B at the same "
                  "memory-limited batch; headline measured separately at "
                  "the realistic batch")


def _child_env():
    """Environment for every child this process starts: this process
    holds the accelerator, so a child that reached for it would fail
    or hang — children run on the CPU."""
    return dict(os.environ, JAX_PLATFORMS="cpu")


def _run(model, cfg, batch_size, num_steps, steps, warmup, run_option,
         wire_stats=None, pipeline_stats=None, metrics_out=None,
         monitor_health=False, compile_out=None):
    import jax
    import numpy as np
    import parallax_tpu as parallax
    from parallax_tpu.models import lm1b

    sess, *_ = parallax.parallel_run(
        model, parallax_config=parallax.Config(
            run_option=run_option, search_partitions=False,
            sparse_grad_mode="slices",
            # compile-ahead engine (ISSUE 3): the batch size is its own
            # bucket (full batches pass through bit-identical — the
            # headline math is untouched) and sess.warmup() below
            # AOT-compiles it before the warmup steps, so compile
            # wall-time lands in compile_out instead of hiding inside
            # the first step
            shape_buckets=[batch_size],
            # health OFF on the timed runs: the in-graph grad-norm would
            # make the headline incomparable to rounds measured without
            # it — main stamps health.* from a separate untimed
            # probe run instead
            monitor_health=monitor_health))
    try:
        rng = np.random.default_rng(0)
        batches = [lm1b.make_batch(rng, batch_size, num_steps,
                                   cfg.vocab_size) for _ in range(4)]
        sess.warmup(feed_dict=batches[0])
        for i in range(warmup):
            sess.run("loss", feed_dict=batches[i % 4])
        if wire_stats is not None:
            wire_stats.update(
                sess.engine.sparse_wire_bytes_per_step())
        jax.block_until_ready(sess.state.params)
        # Steady-state loop through the async pipeline: run_iter preps +
        # places batch t+1 on a background thread while step t runs. The
        # per-step "loss" fetch is LAZY (a Fetch handle — no host<->
        # device round trip, so dispatch never serializes; the old loop
        # had to fetch [] to get the same property); only the last one
        # is materialized, which records the real pipeline-drain time as
        # blocked_on_device. One long window: splitting into best-of-k
        # windows was tried (r5) and REJECTED — the per-window pipeline
        # drain cost more than host-interference noise on every backend.
        # The words count equals the feed's weight sum — the same value
        # the "words" metric computes on device.
        words_per_batch = [float(b["w"].sum()) for b in batches]
        t0 = time.perf_counter()
        words = 0.0
        last = None
        feed = (batches[i % 4] for i in range(steps))
        for i, last in enumerate(sess.run_iter(feed, fetches="loss")):
            words += words_per_batch[i % 4]
        float(last)  # drain: blocks until the final step retires
        jax.block_until_ready(sess.state.params)
        dt = time.perf_counter() - t0
        if pipeline_stats is not None:
            # dispatch-gap / H2D-bytes / blocked-on-device over the
            # measured window (the overlap observability this bench
            # guards; regressions show up as a growing dispatch gap)
            pipeline_stats.update(sess.pipeline_stats.summary())
        if metrics_out is not None:
            # the full metrics-registry snapshot (ISSUE 2): pipeline.*,
            # engine recompiles, health.* (grad norm / loss finiteness),
            # device memory gauges where the backend reports them
            metrics_out.update(sess.metrics_snapshot())
        if compile_out is not None:
            # compile-ahead report (ISSUE 3): bucket signatures,
            # per-bucket AOT compile seconds, executable-/engine-cache
            # hit/miss counts over the measured run
            compile_out.update(sess.compile_stats())
        return words / dt
    finally:
        # free HBM even on OOM so the retry loop's smaller attempt
        # starts clean
        sess.close()
        del sess


def _load_prev_round(root=None):
    """The previous round's bench block: the highest-numbered
    BENCH_r*.json in the repo root, unwrapped from the driver format
    (shared conventions: tools/bench_artifacts.py); None when
    absent/unreadable."""
    here = os.path.dirname(os.path.abspath(__file__))
    root = root or here
    # the helpers live next to THIS file, whatever root is scanned
    sys.path.insert(0, os.path.join(here, "tools"))
    try:
        from bench_artifacts import load_block, round_paths
    except ImportError:
        return None
    paths = round_paths(root)
    return load_block(paths[-1]) if paths else None


def _needs_harness_ab(prev) -> bool:
    """True when this round must record the same-round A/B (VERDICT r5
    item 6): the previous round exists, ran under a DIFFERENT
    bench_version, and left the harness parameters to replay. The A/B
    re-measures the CURRENT build under the previous round's harness
    parameters, so a cross-round delta decomposes into 'methodology
    moved' vs 'the build moved' in-artifact."""
    return (isinstance(prev, dict)
            and prev.get("bench_version") is not None
            and prev.get("bench_version") != BENCH_VERSION
            and isinstance(prev.get("harness"), dict))


def _harness_hash() -> str:
    """sha256 of this file's bytes: two rounds with equal hashes ran
    the IDENTICAL harness, so a headline delta is the build's."""
    import hashlib
    try:
        with open(os.path.abspath(__file__), "rb") as f:
            return hashlib.sha256(f.read()).hexdigest()[:16]
    except OSError:
        return "unknown"


def main():
    import jax

    from parallax_tpu.compile.cache import ensure_persistent_cache
    from parallax_tpu.models import lm1b

    ensure_persistent_cache()

    n_chips = jax.device_count()
    platform = jax.devices()[0].platform
    on_cpu = platform == "cpu"
    if on_cpu:  # local smoke: tiny shapes
        # fp32 compute on CPU: host XLA emulates bf16 matmuls by
        # widening per-op, which is what regressed the r3 fallback
        # number (VERDICT r3 weak item 1) — the bf16 casts are a
        # TPU-MXU optimization with no CPU analogue
        import jax.numpy as jnp
        # the vocab must be big enough for the sampled-vs-full
        # comparison to measure the algorithm, not the harness: at the
        # old vocab=1000 the "dense baseline" was a trivial [N, 1000]
        # matmul and vs_baseline read backwards (r2/r3)
        cfg = lm1b.tiny_config(num_partitions=n_chips,
                               sparse_grad_mode="slices",
                               compute_dtype=jnp.float32,
                               vocab_size=16000, num_samples=128)
        bs, T, steps, warmup = 16 * n_chips, 8, 20, 3
        small_bs = 8 * n_chips
    else:
        bs, T, steps, warmup = 128 * n_chips, 20, 30, 5
        # slices mode: table grads stay (ids, rows) pairs end-to-end —
        # the reference's IndexedSlices processing and the fast path on
        # TPU (no dense [V, D] cotangent / accumulator pass per step).
        # lstm_impl='pallas': the r5 hoisted-input/resident-recurrent
        # kernel serves the flagship (ROADMAP item 17) — default on TPU.
        cfg = lm1b.LM1BConfig(num_partitions=n_chips,
                              sparse_grad_mode="slices",
                              lstm_impl="pallas")
        # full softmax materializes [B*T, 793k] logits; per-chip batch 16
        # is the largest that fits alongside params+opt state in HBM
        small_bs = 16 * n_chips

    # Headline: hybrid engine at the realistic batch size.
    wire = {}
    pipe = {}
    metrics_snap = {}
    compile_snap = {}
    hybrid_wps = _run(lm1b.build_model(cfg), cfg, bs, T, steps, warmup,
                      "HYBRID", wire_stats=wire, pipeline_stats=pipe,
                      metrics_out=metrics_snap, compile_out=compile_snap)
    # Baseline comparison at a common batch size both paths can run. The
    # full-softmax baseline materializes [B*T, V] logits; retry smaller
    # if it doesn't fit rather than losing the whole headline.
    vs_baseline = None
    try_bs = small_bs
    # r5: the comparison pair runs at least 12 steps each — at the old
    # max(5, steps//3) the short full-softmax window made vs_baseline
    # swing ±15% run-to-run on CPU (r4 7.9 vs r5 probes 6.1-6.9)
    cmp_steps = max(12, steps // 2)
    while vs_baseline is None and try_bs >= n_chips:
        try:
            # the OOM-prone full-softmax model goes first so a failed
            # size doesn't waste a measured sampled run
            full_small = _run(lm1b.build_full_softmax_model(cfg), cfg,
                              try_bs, T, cmp_steps, warmup, "HYBRID")
            sampled_small = _run(lm1b.build_model(cfg), cfg, try_bs, T,
                                 cmp_steps, warmup, "HYBRID")
            vs_baseline = sampled_small / full_small
        except Exception as e:  # typically RESOURCE_EXHAUSTED
            print(f"# baseline at bs={try_bs} failed: "
                  f"{type(e).__name__}: {str(e)[:300]}", flush=True)
            try_bs //= 2
    # vs_baseline stays None (JSON null) if the baseline never ran —
    # never fabricate a parity number

    # Health probe (untimed): grad-norm / loss-finite flow through the
    # registry on a short run with monitor_health=True; merged into the
    # stamped snapshot so the BENCH JSON carries them without the
    # in-graph norm compute touching any timed window. Costs one extra
    # engine compile — PARALLAX_BENCH_HEALTH=0 skips it when that
    # matters more than the health keys (e.g. a quick TPU spot-check).
    if os.environ.get("PARALLAX_BENCH_HEALTH", "1") != "0":
        try:
            health_snap = {}
            _run(lm1b.build_model(cfg), cfg, small_bs, T, 6, 2, "HYBRID",
                 metrics_out=health_snap, monitor_health=True)
            metrics_snap.update({k: v for k, v in health_snap.items()
                                 if k.startswith("health.")})
        except Exception as e:
            print(f"# health probe failed: {type(e).__name__}: "
                  f"{str(e)[:200]}", flush=True)

    # Serve section (ISSUE 4): the serving subsystem's own headline —
    # a short mixed-length closed-loop load through ServeSession
    # (tools/loadgen.py), stamped so request-path latency/QPS get a
    # per-round trajectory next to the training headline. Untimed wrt
    # the training windows (runs after them); PARALLAX_BENCH_SERVE=0
    # skips it.
    serve_snap = None
    if os.environ.get("PARALLAX_BENCH_SERVE", "1") != "0":
        try:
            sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
            from tools import loadgen
            ssess, make_feed = loadgen.demo_session(
                max_batch=8, length_buckets=(16, 32), dim=128, layers=2)
            try:
                load = loadgen.run_load(ssess, make_feed, 48,
                                        concurrency=4)
                stats = ssess.stats()
            finally:
                ssess.close()
            occ = stats.get("serve.batch_occupancy") or {}
            step = stats.get("serve.step_ms") or {}
            serve_snap = {
                "requests": load["submitted"],
                "completed": load["completed"],
                "qps": load["qps"],
                "latency_ms": load["latency_ms"],
                "recompiles": stats.get("serve.recompiles", 0),
                "batch_occupancy_mean": round(occ.get("mean", 0), 3)
                if occ else None,
                "step_ms_p50": round(step.get("p50", 0), 3)
                if step else None,
            }
            # Continuous-decode concurrency sweep (ISSUE 6): paged KV +
            # chunked prefill + speculative decode at 1x..8x the r4/r5
            # serve concurrency (max_batch was 8) — tokens/sec and TTFT
            # per offered level, the 8x-64x-concurrency claim as one
            # artifact. PARALLAX_BENCH_SWEEP=0 skips just the sweep.
            if os.environ.get("PARALLAX_BENCH_SWEEP", "1") != "0":
                levels = (8, 16, 32, 64)
                # paged pool, one-dispatch prefill, no speculation:
                # the sweep prices CONCURRENCY (the paged pool's win);
                # chunked prefill trades refill throughput for bounded
                # step stall and speculative economics depend on draft
                # quality — both are priced separately (the SLO guard's
                # decode phase and the decode block's A/Bs)
                rows = loadgen.sweep_decode(
                    levels=levels, speculative=False,
                    prefill_chunk_layers=None, T=32)
                by_level = {r["offered_concurrency"]: r for r in rows}
                # the *_at_8x keys are regression-gated by name
                # (tools/check_regression.py SECONDARY_GATES), so they
                # bind to the literal 8x-of-r4 level (8 * 8 = 64) —
                # absent from a future sweep, they stamp None and the
                # gate SKIPS instead of silently comparing a different
                # concurrency
                at8 = by_level.get(8 * 8)
                best = max((r["tokens_per_sec"] or 0) for r in rows)
                serve_snap["continuous"] = {
                    "sweep": rows,
                    "prev_round_max_concurrency": 8,
                    "max_offered_concurrency": max(levels),
                    "concurrency_multiple": max(levels) // 8,
                    "tokens_per_sec_best": best or None,
                    "ttft_ms_p50_at_8x": ((at8.get("ttft_ms") or {})
                                          .get("p50") if at8 else None),
                    "tokens_per_sec_at_8x": (at8.get("tokens_per_sec")
                                             if at8 else None),
                    "recompiles": sum(r.get("recompiles", 0)
                                      for r in rows),
                    # trace-derived keys (ISSUE 12, obs/reqtrace +
                    # tools/serve_report): per-phase TTFT shares and
                    # the per-percentile dominant-cause report at the
                    # 8x level — report.buckets.p99.* is secondary-
                    # gated by name (tools/check_regression.py)
                    "ttft_decomp": (at8.get("ttft_decomp")
                                    if at8 else None),
                    "deadline_miss_budget_consumed": (
                        at8.get("deadline_miss_budget_consumed")
                        if at8 else None),
                    "report": (at8.get("attribution")
                               if at8 else None),
                }
            # Fleet robustness block (ISSUE 7): the chaos harness run
            # end to end — injected replica crash with failover and a
            # mid-traffic weight hot-swap over a 2-replica decode
            # fleet; failover recovery latency and hot-swap blackout
            # window tracked per round (secondary-gated by
            # tools/check_regression.py). PARALLAX_BENCH_FLEET=0 skips.
            if os.environ.get("PARALLAX_BENCH_FLEET", "1") != "0":
                from tools import check_fleet_faults
                fres = check_fleet_faults.measure()
                fviol = check_fleet_faults.check(fres)
                serve_snap["fleet"] = dict(
                    fres["bench"],
                    ok=not fviol,
                    violations=fviol[:3] or None)
            # Prefix-reuse block (ISSUE 15): the radix-cache guard run
            # end to end at 50% shared-prefix load — warm-vs-cold TTFT
            # p50, tokens/sec with sharing on, hit rate, evictions and
            # the exact-reuse/leak/isolation verdicts, per round.
            # serve.prefix.ttft_ms_p50_warm and .hit_rate are
            # secondary-gated (tools/check_regression.py); no
            # BENCH_VERSION bump (additive block, gates skip when
            # absent). PARALLAX_BENCH_PREFIX=0 skips.
            if os.environ.get("PARALLAX_BENCH_PREFIX", "1") != "0":
                from tools import check_prefix_reuse
                pres = check_prefix_reuse.measure(
                    n_requests=30, prefix_share=0.5)
                pviol = check_prefix_reuse.check(pres)
                serve_snap["prefix"] = {
                    "prefix_share": pres["prefix_share"],
                    "ttft_ms_p50_warm": pres["ttft_ms_p50_warm"],
                    "ttft_ms_p50_cold": pres[
                        "ttft_ms_p50_cold_nosharing"],
                    "tokens_per_sec_warm": pres["tokens_per_sec_warm"],
                    "tokens_per_sec_nosharing": pres[
                        "tokens_per_sec_nosharing"],
                    "hit_rate": pres["hit_rate"],
                    "full_hits": pres["full_hits"],
                    "cow_copies": pres["cow_copies"],
                    "evictions": pres["evictions"],
                    "token_mismatches": pres["token_mismatches"],
                    "tenant_isolation_clean": pres[
                        "tenant_isolation"].get("b_hits_delta") == 0,
                    "ok": not pviol,
                    "violations": pviol[:3] or None,
                }
            # Disaggregation A/B block (ISSUE 19): colocated ServeFleet
            # vs DisaggFleet (prefill pool -> wire transfer -> decode
            # pool) replaying the SAME mixed-regime request stream —
            # long-prefill/short-decode mixed with short-prefill/long-
            # decode, the traffic shape that pulls a colocated replica
            # in opposite directions. serve.disagg.ttft_ms_p99 and
            # serve.disagg.tokens_per_sec are secondary-gated
            # (tools/check_regression.py); no BENCH_VERSION bump
            # (additive block, gates skip when absent).
            # PARALLAX_BENCH_DISAGG=0 skips.
            if os.environ.get("PARALLAX_BENCH_DISAGG", "1") != "0":
                from parallax_tpu.serve import (DisaggFleet,
                                                FleetConfig,
                                                ServeFleet)
                mk = loadgen.demo_disagg_rig(slots=4)
                dfeed, dmnt = loadgen.mixed_regime_feed(vocab=64)
                n_req = 24

                colo = ServeFleet(mk, config=FleetConfig(
                    num_replicas=2, min_replicas=1))
                try:
                    # unmeasured warmup drains: first-touch lazy init
                    # on each arm's serving path would otherwise land
                    # a ~1s bimodal spike in the gated p99
                    for i in range(2):
                        colo.submit(dfeed(i), max_new_tokens=dmnt(i)
                                    ).result(timeout=120)
                    crep = loadgen.run_load(
                        colo, dfeed, n_requests=n_req, concurrency=4,
                        max_new_tokens=dmnt)
                finally:
                    colo.close()

                dis = DisaggFleet(
                    mk, mk,
                    prefill_config=FleetConfig(num_replicas=1,
                                               min_replicas=1),
                    decode_config=FleetConfig(num_replicas=1,
                                              min_replicas=1))
                try:
                    for i in range(2):
                        dis.submit(dfeed(i), max_new_tokens=dmnt(i)
                                   ).result(timeout=120)
                    drep = loadgen.run_load(
                        dis, dfeed, n_requests=n_req, concurrency=4,
                        max_new_tokens=dmnt)
                    dsnap = dis.metrics.snapshot()
                    drecomp = dis.recompiles()
                finally:
                    dis.close()

                def _arm(rep):
                    return {
                        "completed": rep["completed"],
                        "tokens_per_sec": rep["tokens_per_sec"],
                        "ttft_ms_p50": rep["ttft_ms"]["p50"],
                        "ttft_ms_p99": rep["ttft_ms"]["p99"],
                    }

                tms = dsnap.get("serve.disagg.transfer_ms") or {}
                pms = dsnap.get("serve.disagg.prefill_ms") or {}
                serve_snap["disagg"] = {
                    "colocated": _arm(crep),
                    "disaggregated": _arm(drep),
                    # gate-addressable copies of the disaggregated
                    # arm: serve.disagg.ttft_ms_p99 and
                    # serve.disagg.tokens_per_sec resolve here
                    "ttft_ms_p99": drep["ttft_ms"]["p99"],
                    "tokens_per_sec": drep["tokens_per_sec"],
                    "transfers": dsnap.get("serve.disagg.transfers"),
                    "transfer_bytes": dsnap.get(
                        "serve.disagg.transfer_bytes"),
                    "transfer_ms_p50": tms.get("p50"),
                    "transfer_ms_mean": tms.get("mean"),
                    "prefill_ms_p50": pms.get("p50"),
                    "prefill_fallbacks": dsnap.get(
                        "serve.disagg.prefill_fallbacks"),
                    "recompiles": drecomp,
                    # the caveat lives IN the artifact so a reader of
                    # bench.json sees it without the docs
                    "note": ("single-process CPU arms: the 'wire' is "
                             "a host memcpy and both pools share one "
                             "machine, so the colocated-vs-disagg "
                             "verdict does not transfer to TPUs; "
                             "cross-round drift of the gated keys is "
                             "the signal, not the A/B winner"),
                }
        except Exception as e:
            print(f"# serve bench failed: {type(e).__name__}: "
                  f"{str(e)[:200]}", flush=True)

    # Decode block (VERDICT r5 satellite + ISSUE 6): cached-vs-
    # cacheless NMT decode ratios plus the paged-vs-dense and
    # speculative-vs-plain A/Bs (tools/nmt_decode_timing.py) — every
    # serve-side latency primitive tracked per round instead of a
    # one-off perf file. PARALLAX_BENCH_DECODE=0 skips it.
    decode_snap = None
    if os.environ.get("PARALLAX_BENCH_DECODE", "1") != "0":
        try:
            from tools import nmt_decode_timing
            d = nmt_decode_timing.measure(lengths=(32, 64), batch=4,
                                          repeats=2)
            decode_snap = {
                "rows": d["rows"],
                "ratio_grows_with_T": d["ratio_grows_with_T"],
                "paged_vs_dense": d.get("paged_vs_dense"),
                "spec_vs_plain": d.get("spec_vs_plain"),
                "spec_ceiling": d.get("spec_ceiling"),
            }
        except Exception as e:
            print(f"# decode bench failed: {type(e).__name__}: "
                  f"{str(e)[:200]}", flush=True)

    # LSTM backward block (ISSUE 14): the flagship recurrence's
    # fwd+bwd A/B — pallas backward kernel vs the recompute-XLA VJP —
    # at op level and through one real LM1B training step, plus the
    # analytic fwd+bwd HBM-bytes story at the true flagship shape.
    # Off-TPU the pallas programs run interpreted, so the measured
    # ratios carry the interpret-tax witness and the CPU-relative
    # caveat in-artifact; tools/check_regression.py secondary-gates
    # lstm.op_ms.pallas_bwd and (drift) lstm.pallas_over_recompute.
    # PARALLAX_BENCH_LSTM=0 skips.
    lstm_snap = None
    if os.environ.get("PARALLAX_BENCH_LSTM", "1") != "0":
        try:
            sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
            from tools import bench_lstm
            lstm_snap = bench_lstm.measure()
        except Exception as e:
            print(f"# lstm bench failed: {type(e).__name__}: "
                  f"{str(e)[:200]}", flush=True)

    # Paged-attention block (ISSUE 16): one paged decode-step
    # attention A/B — fused Pallas kernel (live pages only) vs the
    # full-width einsum gather — across pool occupancies, plus the
    # analytic allocated-pages-only vs full-width HBM table at the
    # flagship decode shape. Off-TPU the kernel runs interpreted, so
    # the measured ratios carry the interpret-tax witness (the
    # equal-bytes 100%-occupancy ratio) and the CPU-relative caveat
    # in-artifact; tools/check_regression.py secondary-gates
    # attn.step_ms.kernel and (drift) attn.kernel_over_einsum.
    # PARALLAX_BENCH_ATTN=0 skips.
    attn_snap = None
    if os.environ.get("PARALLAX_BENCH_ATTN", "1") != "0":
        try:
            sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
            from tools import bench_paged_attn
            attn_snap = bench_paged_attn.measure()
        except Exception as e:
            print(f"# attn bench failed: {type(e).__name__}: "
                  f"{str(e)[:200]}", flush=True)

    # Auto-tuner block (ISSUE 10): one MeshSearch decision end to end
    # on the smoke-scale flagship — candidates enumerated / pruned /
    # trialed, predicted-vs-measured ms for the measured winner,
    # search wall seconds and the engine-cache counters that prove
    # trials reuse compiles. tools/check_regression.py secondary-gates
    # tune.search_seconds and (two-sided) tune.predicted_over_measured
    # drift. Runs in a SUBPROCESS (tools/bench_tune.py): a multi-mesh
    # search in-process is the known XLA:CPU hard-crash workload, and
    # an abort must cost this round its tune block, not the whole
    # artifact. The child runs on the CPU (this process holds the
    # chip; the block stamps its platform), so the ratio is
    # CPU-relative — cross-round DRIFT is the gated
    # signal, never the absolute value. PARALLAX_BENCH_TUNE=0 skips.
    tune_snap = None
    if os.environ.get("PARALLAX_BENCH_TUNE", "1") != "0":
        try:
            here = os.path.dirname(os.path.abspath(__file__))
            proc = subprocess.run(
                [sys.executable, os.path.join(here, "tools",
                                              "bench_tune.py")],
                env=_child_env(), capture_output=True, text=True,
                timeout=600)
            start = proc.stdout.find("{")
            if proc.returncode == 0 and start >= 0:
                tune_snap = json.loads(proc.stdout[start:])
            else:
                print(f"# tune bench child failed rc="
                      f"{proc.returncode}: "
                      f"{(proc.stderr or '')[-200:]}", flush=True)
        except Exception as e:
            print(f"# tune bench failed: {type(e).__name__}: "
                  f"{str(e)[:200]}", flush=True)

    # Plan-observatory block (ISSUE 13): one profiled window end to
    # end on the embedding rig — measured per-op attribution shares,
    # coverage vs the device step wall with the residual explicit,
    # and the per-term calibration ratios (predicted/measured for the
    # on-chip and wire roofline terms). tools/check_regression.py
    # secondary-gates profile.attribution_coverage and (two-sided)
    # the wire calibration drift — the ratio is CPU-relative off-TPU,
    # so cross-round DRIFT is the gated signal, never the absolute.
    # Subprocess child (tools/check_profile_attrib.py — the same
    # tier-1 guard): jax.profiler capture is process-global state an
    # abort must not leak into the headline. PARALLAX_BENCH_PROFILE=0
    # skips. No BENCH_VERSION bump: new block, gate-side skip.
    profile_snap = None
    if os.environ.get("PARALLAX_BENCH_PROFILE", "1") != "0":
        try:
            here = os.path.dirname(os.path.abspath(__file__))
            proc = subprocess.run(
                [sys.executable,
                 os.path.join(here, "tools",
                              "check_profile_attrib.py")],
                env=_child_env(), capture_output=True, text=True,
                timeout=600)
            start = proc.stdout.find("{")
            if start >= 0:
                profile_snap = json.loads(proc.stdout[start:])
                if proc.returncode != 0:
                    print(f"# profile guard violations: "
                          f"{profile_snap.get('violations')}",
                          flush=True)
            else:
                print(f"# profile bench child failed rc="
                      f"{proc.returncode}: "
                      f"{(proc.stderr or '')[-200:]}", flush=True)
        except Exception as e:
            print(f"# profile bench failed: {type(e).__name__}: "
                  f"{str(e)[:200]}", flush=True)

    # Checkpoint cost block (ISSUE 9): save/restore latency, bytes,
    # and the async-save step-overhead A/B (async critical-path cost
    # vs the synchronous path, amortized over the save cadence —
    # tools/bench_ckpt.py, budget <= 2%). The chaos-harness outcome
    # (tools/check_train_faults.py) rides along so every round proves
    # SIGKILL-exact-resume / torn-fallback / NaN-rollback still hold.
    # PARALLAX_BENCH_CKPT=0 skips; check_regression secondary-gates
    # ckpt.save_ms / ckpt.restore_ms between compatible rounds.
    ckpt_snap = None
    if os.environ.get("PARALLAX_BENCH_CKPT", "1") != "0":
        try:
            sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
            from tools import bench_ckpt
            ckpt_snap = bench_ckpt.measure()
            if os.environ.get("PARALLAX_BENCH_CKPT_FAULTS", "1") != "0":
                from tools import check_train_faults
                cres = check_train_faults.measure()
                cviol = check_train_faults.check(cres)
                ckpt_snap["faults"] = dict(
                    cres["bench"], ok=not cviol,
                    violations=cviol[:3] or None)
        except Exception as e:
            print(f"# ckpt bench failed: {type(e).__name__}: "
                  f"{str(e)[:200]}", flush=True)

    # Numerics observatory block (ISSUE 17): per-layer stats trail
    # analysis on the sampled simple-model rig (which layer, which
    # risk), both kernel-drift sentinels clean AND with an injected
    # perturbation (clean must stay silent, perturbed must flag), and
    # the host-side per-sample consume cost. tools/check_regression.py
    # secondary-gates the sentinels' accuracy (two-sided drift: the
    # agreement is CPU-relative under Pallas interpret mode, so
    # cross-round DRIFT is the signal) and numerics.consume_us.
    # PARALLAX_BENCH_NUMERICS=0 skips. No BENCH_VERSION bump: new
    # block, gate-side skip.
    numerics_snap = None
    if os.environ.get("PARALLAX_BENCH_NUMERICS", "1") != "0":
        try:
            sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
            from tools import numerics_report
            numerics_snap = numerics_report.measure()
        except Exception as e:
            print(f"# numerics bench failed: {type(e).__name__}: "
                  f"{str(e)[:200]}", flush=True)

    # Ops observatory block (ISSUE 20): the run-lifetime goodput
    # fraction and badput breakdown from the chaos rig
    # (tools/check_goodput.py: clean / SIGKILL-resume / NaN-rollback
    # children, each account summing to wall by construction), plus
    # the journal-emit and alert-eval unit costs priced standalone.
    # tools/check_regression.py secondary-gates ops.goodput_fraction
    # (a falling fraction means the instrumented loop is losing wall
    # to badput) and ops.alert_eval_us (a full rule pass creeping up).
    # Absolutes are CPU-relative. PARALLAX_BENCH_OPS=0 skips. No
    # BENCH_VERSION bump: new block, gate-side skip.
    ops_snap = None
    if os.environ.get("PARALLAX_BENCH_OPS", "1") != "0":
        try:
            sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
            from parallax_tpu import obs
            from tools import check_goodput
            from tools.check_obs_overhead import _unit_cost_us
            gres = check_goodput.measure()
            gviol = check_goodput.check(gres)
            jr = obs.EventJournal(capacity=64,
                                  registry=obs.MetricsRegistry())
            eng = obs.AlertEngine(obs.MetricsRegistry(),
                                  rules=obs.builtin_rules(),
                                  interval_s=3600.0)
            ops_snap = dict(
                gres["bench"],
                goodput_fraction=gres["bench"]
                ["clean_goodput_fraction"],
                journal_emit_us=round(_unit_cost_us(
                    lambda: jr.emit("bench", "tick", n=1)), 3),
                alert_eval_us=round(_unit_cost_us(
                    eng.evaluate, iters=200, batches=5), 3),
                chaos_ok=not gviol,
                violations=gviol[:3] or None)
        except Exception as e:
            print(f"# ops bench failed: {type(e).__name__}: "
                  f"{str(e)[:200]}", flush=True)

    per_chip = hybrid_wps / n_chips

    # Same-round A/B on a bench_version bump (VERDICT r5 item 6): the
    # CURRENT build re-measured under the PREVIOUS round's harness
    # parameters. The pair (value, value_under_prev_params) separates
    # "the methodology moved the number" from "the build moved the
    # number" — the r4→r5 −23% had neither. PARALLAX_BENCH_AB=0 skips.
    ab_snap = None
    prev = _load_prev_round()
    if (_needs_harness_ab(prev)
            and os.environ.get("PARALLAX_BENCH_AB", "1") != "0"):
        try:
            ph = prev["harness"]
            ab_wps = _run(lm1b.build_model(cfg), cfg,
                          int(ph.get("batch_size", bs)),
                          int(ph.get("seq_len", T)),
                          int(ph.get("steps_measured", steps)),
                          int(ph.get("warmup_steps", warmup)), "HYBRID")
            ab_per_chip = ab_wps / n_chips
            ab_snap = {
                "prev_bench_version": prev.get("bench_version"),
                "prev_value": prev.get("value"),
                "prev_harness_sha256": ph.get("bench_sha256"),
                "prev_vocab_size": ph.get("vocab_size"),
                "value_under_prev_params": round(ab_per_chip, 1),
                "value_current_params": round(per_chip, 1),
                "current_over_prev_params": round(
                    per_chip / ab_per_chip, 3) if ab_per_chip else None,
                "note": ("same build, previous round's harness params "
                         "(batch/seq/steps/warmup; vocab stays "
                         "current) — attributes methodology vs build "
                         "moves"),
            }
        except Exception as e:
            print(f"# harness A/B failed: {type(e).__name__}: "
                  f"{str(e)[:200]}", flush=True)
    # MFU: analytic matmul FLOPs per word (fwd+bwd) over the chip's
    # published bf16 peak — the judged utilization number (VERDICT r2
    # item 2). Null on CPU / unknown hardware, never fabricated.
    from parallax_tpu.common import flops as flops_lib
    fpw = flops_lib.lm1b_matmul_flops_per_word(cfg)
    # device_peak_flops owns the platform gate: null off the TPU, the
    # table's peak on it, and an unknown TPU kind raises
    peak = flops_lib.device_peak_flops(
        platform, jax.devices()[0].device_kind)
    mfu = flops_lib.mfu(fpw, per_chip, peak)
    result = {
        "metric": "lm1b_words_per_sec_per_chip",
        "value": round(per_chip, 1),
        "unit": "words/sec/chip",
        "vs_baseline": (round(vs_baseline, 3)
                        if vs_baseline is not None else None),
        "bench_version": BENCH_VERSION,
        "baseline_basis": BASELINE_BASIS,
        "platform": platform,
        "n_chips": n_chips,
        "flops_per_word": fpw,
        "flops_per_step": fpw * bs * T,
        "device_peak_flops": peak,
        "mfu": round(mfu, 4) if mfu is not None else None,
        # async-pipeline health over the headline window. Kept ALONGSIDE
        # the registry snapshot below (which carries the same pipeline.*
        # data in histogram form) for cross-round continuity: BENCH_r0x
        # consumers read this key; drop it once comparisons re-baseline.
        "pipeline": pipe or None,
        # metrics-registry snapshot over the headline window (obs/):
        # pipeline.* overlap signals, steps/sec, engine recompiles,
        # health grad-norm / loss-finite (untimed probe run), device
        # memory when the backend reports it
        "metrics": metrics_snap or None,
        # compile-ahead engine over the headline run (ISSUE 3): bucket
        # signatures, per-bucket AOT warmup compile seconds, and the
        # executable-/engine-cache hit/miss counts — a healthy run
        # shows zero executable misses and engine.recompiles == 0 in
        # the metrics snapshot above
        "compile": compile_snap or None,
        # online serving (ISSUE 4): ServeSession QPS/latency under the
        # loadgen mixed-length closed loop, recompiles (healthy: 0)
        "serve": serve_snap,
        # KV-cached vs cache-less decode ratios (the serve-side latency
        # primitive), tracked per round
        "decode": decode_snap,
        # pallas LSTM backward A/B (ISSUE 14): kernel vs recompute-XLA
        # fwd+bwd step_ms (CPU-relative off-TPU, interpret-tax witness
        # stamped) + the analytic flagship HBM-bytes story
        "lstm": lstm_snap,
        # paged-attention decode A/B (ISSUE 16): fused Pallas kernel
        # vs full-width einsum gather across pool occupancies
        # (CPU-relative off-TPU, interpret-tax witness stamped) + the
        # analytic live-pages-only vs gather HBM table at the
        # flagship decode shape
        "attn": attn_snap,
        # checkpoint/recovery costs (ISSUE 9): save/restore latency,
        # bytes, async-vs-sync step-overhead A/B, chaos-harness outcome
        "ckpt": ckpt_snap,
        # auto-tuner v2 (ISSUE 10): one MeshSearch decision — plans
        # enumerated/pruned/trialed, winner predicted-vs-measured ms
        # (CPU-relative off-TPU), search wall seconds, cache hits
        "tune": tune_snap,
        # plan observatory (ISSUE 13): measured per-op attribution of
        # one profiled window (coverage vs device step wall, residual
        # explicit, category shares, dense/sparse split) + per-term
        # cost-model calibration ratios (CPU-relative off-TPU)
        "profile": profile_snap,
        # numerics observatory (ISSUE 17): per-layer stats attribution
        # on the sampled rig, drift-sentinel clean/perturbed self-test
        # (CPU-relative interpret-mode agreement), host consume cost
        "numerics": numerics_snap,
        # ops observatory (ISSUE 20): run-lifetime goodput fraction +
        # badput breakdown from the chaos rig, journal-emit /
        # alert-eval unit costs (CPU-relative)
        "ops": ops_snap,
        # same-round A/B under the previous round's harness params,
        # recorded iff bench_version bumped this round (VERDICT r5
        # item 6); tools/check_regression.py requires it to treat a
        # version-bump delta as explained
        "ab_vs_prev_harness": ab_snap,
        # harness provenance (VERDICT r5 item 6): exactly what this
        # number was measured with, so cross-round deltas are
        # attributable when the bench harness itself changes — compare
        # values only between rounds whose harness blocks match
        "harness": {
            "bench_sha256": _harness_hash(),
            "steps_measured": steps,
            "warmup_steps": warmup,
            "batch_size": bs,
            "seq_len": T,
            "vocab_size": cfg.vocab_size,
            "n_feed_batches": 4,
            "baseline_batch_size": small_bs,
            "baseline_steps": cmp_steps,
        },
    }
    if wire.get("dense_allreduce_bytes"):
        # north-star secondary metric: sparse-grad bytes on wire per step
        # vs shipping dense [V, D] gradients
        result["sparse_grad_bytes_on_wire"] = wire["sparse_path_bytes"]
        result["dense_grad_bytes_equivalent"] = \
            wire["dense_allreduce_bytes"]
    if on_cpu:
        # The CPU smoke config is still orders of magnitude below the
        # flagship's 793k vocab, so always attach the FLAGSHIP
        # wire-bytes accounting too; it's trace-time-exact and costs one
        # abstract eval.
        try:
            sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
            from tools.wire_bytes_report import flagship_accounting
            flag = flagship_accounting(n_chips)
            result["flagship_wire_bytes"] = {
                "sparse_path_bytes": flag["sparse_path_bytes"],
                "dense_allreduce_bytes": flag["dense_allreduce_bytes"],
                "sparse_over_dense": flag["sparse_over_dense"],
            }
            # the tuned configuration (bf16 row planes + per-table
            # overflow-free dedup capacities): 0.65% of the reference's
            # fp32 dense all-reduce — perf/WIRE_BYTES_r04.json has the
            # full accounting
            opt = flagship_accounting(n_chips, table_dtype="bfloat16",
                                      dedup_capacity="auto")
            result["flagship_wire_bytes_optimized"] = {
                "table_dtype": "bfloat16",
                "dedup_capacity": opt["config"]["dedup_capacity"],
                "overflow_free":
                    opt["config"]["dedup_capacity_overflow_free"],
                "sparse_path_bytes": opt["sparse_path_bytes"],
                "dense_fp32_reference_bytes":
                    opt["dense_fp32_reference_bytes"],
                "sparse_over_dense_fp32_ref":
                    opt["sparse_over_dense_fp32_ref"],
            }
        except Exception as e:
            print(f"# flagship wire accounting failed: {e}", flush=True)
    print(json.dumps(result))


if __name__ == "__main__":
    main()
