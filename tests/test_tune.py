"""Auto-tuner v2 (parallax_tpu.tune, ISSUE 10).

Three layers of coverage:

* the PURE cost model — hand-computed FLOPs/bytes/wire terms on toy
  inputs, no jax involved (the model's whole point is being checkable
  on paper);
* plan/TuneConfig validation — bad dp*tp products, unknown run
  options, top_k < 1 etc. all refuse loudly;
* the session integration seams that must not regress: the
  plan-aware engine-cache key (two same-count/different-shape plans
  get distinct engines; an exact re-request hits), and the
  wire-summary refactor keeping tools/wire_bytes_report.py's output
  bit-identical (golden-diffed against the inlined math it replaced).

The measured end-to-end search (full enumeration, top-k trial
counting, winner quality, rank correlation vs exhaustive measurement)
runs in tests/mesh_search_driver.py — a subprocess, because a
multi-mesh search stacked on this suite's in-process state
intermittently hard-crashes the XLA:CPU toolchain (same isolation as
compile_search_driver.py).
"""

import os
import subprocess
import sys

import numpy as np
import pytest

import parallax_tpu as parallax
from parallax_tpu.common import consts
from parallax_tpu.tune import costmodel
from parallax_tpu.tune.costmodel import CostInputs, Plan
from parallax_tpu.tune.search import MeshSearch, emittable_plans, \
    enumerate_plans


# -- the pure cost model --------------------------------------------------


def _inputs(**kw):
    base = dict(flops=8e9, hbm_bytes=4e9, dense_grad_bytes=1_000_000,
                table_grad_bytes=64_000_000, sparse_fwd_bytes=2_000_000,
                sparse_repl_bytes=0, probe_dp=1, probe_tp=8,
                num_devices=8, peak_flops=1e12, hbm_bps=1e11,
                ici_bps=1e10, peak_is_nominal=False)
    base.update(kw)
    return CostInputs(**base)


class TestCostModelTerms:
    def test_compute_and_hbm_terms_hand_computed(self):
        pc = costmodel.predict(Plan(1, 8, "HYBRID"), _inputs())
        # 8e9 FLOPs over 8 devices at 1e12 each -> 1 ms
        assert pc.terms["compute_s"] == pytest.approx(1e-3)
        # 4e9 bytes over 8 devices at 1e11 B/s each -> 5 ms
        assert pc.terms["hbm_s"] == pytest.approx(5e-3)
        # compute and HBM overlap: the binding ceiling is HBM
        wire = (pc.terms["wire_dense_s"] + pc.terms["wire_zero_shard_s"]
                + pc.terms["wire_table_s"])
        assert pc.total_s == pytest.approx(5e-3 + wire)

    def test_dense_ring_term_hand_computed(self):
        # ring all-reduce of 1 MB over 8 devices: 2 * 1e6 * 7/8 bytes
        # across the mesh, over 8 * 1e10 B/s aggregate
        pc = costmodel.predict(Plan(1, 8, "HYBRID"), _inputs())
        want = 2 * 1_000_000 * (7 / 8) / (8 * 1e10)
        assert pc.terms["wire_dense_s"] == pytest.approx(want)

    def test_ar_pays_dense_table_ring(self):
        inp = _inputs()
        ar = costmodel.predict(Plan(8, 1, "AR"), inp)
        want = 2 * 64_000_000 * (7 / 8) / (8 * 1e10)
        assert ar.terms["wire_table_s"] == pytest.approx(want)
        hy = costmodel.predict(Plan(1, 8, "HYBRID"), inp)
        # the sparse exchange (2 MB recorded) is far below the dense
        # [V, D] ring (128 MB moved) — the paper's core claim, in
        # model form
        assert hy.terms["wire_table_s"] < ar.terms["wire_table_s"] / 10
        assert hy.total_s < ar.total_s

    def test_sparse_term_rescales_with_tp(self):
        inp = _inputs(probe_tp=8)
        t8 = costmodel.predict(Plan(1, 8, "HYBRID"), inp)
        t2 = costmodel.predict(Plan(4, 2, "HYBRID"), inp)
        # recorded at tp=8 (fraction 7/8); at tp=2 the exchange
        # fraction is 1/2 -> bytes scale by (1/2)/(7/8) = 4/7, but the
        # tp=2 plan also pays the repl-combine estimate over dp=4
        fwd8 = 2_000_000 * (7 / 8) / (7 / 8)
        fwd2 = 2_000_000 * (1 / 2) / (7 / 8)
        repl2 = 2 * (64_000_000 / 2) * (3 / 4)
        assert t8.terms["wire_table_s"] == pytest.approx(
            fwd8 / (8 * 1e10))
        assert t2.terms["wire_table_s"] == pytest.approx(
            (fwd2 + repl2) / (8 * 1e10))

    def test_shard_pays_zero_gather_tax(self):
        inp = _inputs()
        sh = costmodel.predict(Plan(1, 8, "SHARD"), inp)
        hy = costmodel.predict(Plan(1, 8, "HYBRID"), inp)
        want = 2 * 1_000_000 * (7 / 8) / (8 * 1e10)
        assert sh.terms["wire_zero_shard_s"] == pytest.approx(want)
        assert hy.terms["wire_zero_shard_s"] == 0.0
        assert sh.total_s > hy.total_s

    def test_async_hides_wire_behind_compute(self):
        inp = _inputs()
        sync = costmodel.predict(Plan(1, 8, "HYBRID", sync=True), inp)
        asyn = costmodel.predict(Plan(1, 8, "HYBRID", sync=False), inp)
        assert asyn.terms["wire_hidden_s"] > 0
        assert asyn.total_s < sync.total_s
        # hiding is capped by the compute term
        assert asyn.terms["wire_hidden_s"] <= \
            sync.terms["compute_s"] + 1e-12

    def test_nominal_fallback_keeps_ranking_usable(self):
        inp = _inputs(peak_flops=None, hbm_bps=None, ici_bps=None,
                      peak_is_nominal=True)
        pc = costmodel.predict(Plan(1, 8, "HYBRID"), inp)
        assert pc.total_s > 0
        assert inp.resolved().peak_flops == costmodel.NOMINAL_PEAK_FLOPS

    def test_lookup_wire_bytes_hand_computed(self):
        # [V=100, D=16] table, 24 ids, 24 counts, 128 repl bytes, bf16
        # rows: ids 24*4 + rows 2*24*16*2 + counts 24*4 + repl 128
        got = costmodel.lookup_wire_bytes((100, 16), 24, 24, 128, 2)
        assert got == 24 * 4 + 2 * 24 * 16 * 2 + 24 * 4 + 128

    def test_dense_alternative_bytes_hand_computed(self):
        assert costmodel.dense_alternative_bytes((100, 16), 4) == \
            2 * 100 * 16 * 4


# -- wire_summary: the refactored wire_bytes_report math ------------------


class TestWireSummary:
    def test_golden_diff_vs_inlined_math(self):
        """The exact expressions tools/wire_bytes_report.py used to
        inline, on a representative accounting dict."""
        wire = {"sparse_path_bytes": 123_456,
                "dense_allreduce_bytes": 10_000_000}
        for elem in (4, 2):
            got = costmodel.wire_summary(wire, table_elem_bytes=elem)
            dense_fp32_ref = wire["dense_allreduce_bytes"] * 4 // elem
            assert got["dense_fp32_reference_bytes"] == dense_fp32_ref
            assert got["sparse_over_dense"] == pytest.approx(
                wire["sparse_path_bytes"]
                / wire["dense_allreduce_bytes"])
            assert got["sparse_over_dense_fp32_ref"] == pytest.approx(
                wire["sparse_path_bytes"] / dense_fp32_ref)

    def test_zero_dense_yields_none_ratios(self):
        got = costmodel.wire_summary({"sparse_path_bytes": 5,
                                      "dense_allreduce_bytes": 0})
        assert got["sparse_over_dense"] is None
        assert got["sparse_over_dense_fp32_ref"] is None
        assert got["dense_fp32_reference_bytes"] == 0

    def test_pipeline_section_golden_diff_vs_costmodel(self):
        """tools/wire_bytes_report.py's per-plan pipeline section is
        the one wire owner's output verbatim — golden-diffed per plan
        against direct pipeline_wire_bytes calls (ISSUE 18
        satellite)."""
        from tools.wire_bytes_report import pipeline_plan_section

        rec = dict(schedule="1f1b", microbatches=4, virtual_stages=1,
                   pinned_stages=None, num_layers=8, model_dim=32,
                   act_itemsize=4, act_bytes=65536, global_batch=32)
        got = pipeline_plan_section(rec, num_devices=8)
        assert got["act_bytes_per_boundary"] == 65536
        rows = {r["plan"]: r for r in got["plans"]}
        assert rows and all(r["pp"] > 1 for r in rows.values())
        from parallax_tpu.tune.search import emittable_plans as ep
        for plan in ep(8, max_pp=8, pipeline=rec):
            if plan.pp == 1:
                continue
            want = costmodel.pipeline_wire_bytes(
                65536, 4, plan.pp, plan.virtual_stages,
                schedule="1f1b", dp=plan.dp, tp=plan.tp)
            row = rows[plan.describe()]
            for k in ("per_hop_bytes", "activation_bytes",
                      "cotangent_bytes", "total_bytes", "ticks",
                      "bubble_fraction", "microbatches_scheduled"):
                assert row[k] == want[k], (plan.describe(), k)
            # 1f1b: the cotangent stream mirrors the activations
            assert row["cotangent_bytes"] == row["activation_bytes"]
        # missing act_bytes falls back to the derivable product, same
        # as costmodel.predict
        rec2 = dict(rec, act_bytes=None)
        got2 = pipeline_plan_section(rec2, num_devices=8)
        assert got2["act_bytes_per_boundary"] == 32 * 32 * 4


# -- plan / config validation ---------------------------------------------


class TestValidation:
    def test_plan_refuses_bad_product(self):
        with pytest.raises(ValueError, match="dp\\*tp"):
            Plan(3, 2).validate_for(8)
        Plan(4, 2).validate_for(8)  # ok

    def test_plan_refuses_nonpositive_axes(self):
        with pytest.raises(ValueError):
            Plan(0, 8)
        with pytest.raises(ValueError):
            Plan(2, -1)

    def test_plan_normalizes_legacy_run_options(self):
        assert Plan(1, 8, "PS").run_option == consts.RUN_SHARD
        assert Plan(8, 1, "mpi").run_option == consts.RUN_AR

    def test_plan_refuses_unknown_run_option(self):
        with pytest.raises(ValueError, match="run_option"):
            Plan(1, 8, "RING")

    def test_tune_config_refuses_bad_top_k(self):
        with pytest.raises(ValueError, match="top_k"):
            parallax.TuneConfig(top_k=0)

    def test_tune_config_refuses_unknown_run_option(self):
        with pytest.raises(ValueError, match="run_option"):
            parallax.TuneConfig(run_options=("AR", "NOPE"))

    def test_tune_config_refuses_empty_run_options(self):
        with pytest.raises(ValueError, match="at least one"):
            parallax.TuneConfig(run_options=())

    def test_tune_config_refuses_bad_trial_window(self):
        with pytest.raises(ValueError, match="trial_steps"):
            parallax.TuneConfig(trial_steps=3, trial_warmup=3)
        with pytest.raises(ValueError, match="trial_warmup"):
            parallax.TuneConfig(trial_warmup=-1)

    def test_tune_config_refuses_bad_tp_bounds(self):
        with pytest.raises(ValueError, match="min_tp"):
            parallax.TuneConfig(min_tp=0)
        with pytest.raises(ValueError, match="max_tp"):
            parallax.TuneConfig(min_tp=4, max_tp=2)

    def test_tune_config_refuses_bad_constants(self):
        with pytest.raises(ValueError, match="ici_gbps"):
            parallax.TuneConfig(ici_gbps=0)

    def test_parallax_config_refuses_non_tuneconfig(self):
        with pytest.raises(ValueError, match="tune_config"):
            parallax.Config(tune_config={"top_k": 3})

    def test_mesh_search_refuses_mismatched_base_plan(self):
        with pytest.raises(ValueError, match="dp\\*tp"):
            MeshSearch(8, parallax.TuneConfig(), Plan(2, 2))

    def test_mesh_search_refuses_empty_plan_space(self):
        """tp bounds that bracket no divisor (with AR excluded) must
        refuse at construction with the cause — not IndexError from
        the session's first run()."""
        with pytest.raises(ValueError, match="admits no plan"):
            MeshSearch(8, parallax.TuneConfig(
                run_options=("SHARD",), min_tp=3, max_tp=3),
                Plan(1, 8, "SHARD"))
        # AR's canonical tp=1 plan qualifies whatever the bounds
        MeshSearch(8, parallax.TuneConfig(
            run_options=("AR", "SHARD"), min_tp=3, max_tp=3),
            Plan(1, 8, "SHARD"))


# -- enumeration ----------------------------------------------------------


class TestEnumeration:
    def test_full_space_is_divisors_times_options(self):
        plans = enumerate_plans(8)
        # divisors {1, 2, 4, 8} x {AR, SHARD, HYBRID}
        assert len(plans) == 12
        assert all(p.dp * p.tp == 8 for p in plans)

    def test_emittable_dedupes_equivalent_plans(self):
        plans = emittable_plans(8)
        # one replicated canonical (AR@tp1) + {SHARD, HYBRID} x
        # tp in {2, 4, 8}
        assert len(plans) == 7
        descs = [p.describe() for p in plans]
        assert descs.count("dp8xtp1/AR") == 1
        # AR is shard-axis-blind: no AR plan off its canonical tp=1
        assert not any(p.run_option == consts.RUN_AR and p.tp != 1
                       for p in plans)
        assert len(set(descs)) == len(descs)

    def test_tp_bounds_respected(self):
        plans = emittable_plans(8, min_tp=4)
        assert all(p.tp >= 4 or p.run_option == consts.RUN_AR
                   for p in plans)
        plans = emittable_plans(8, max_tp=2)
        assert all(p.tp <= 2 for p in plans)

    def test_run_option_subset(self):
        plans = emittable_plans(8, run_options=("HYBRID",))
        assert all(p.run_option == consts.RUN_HYBRID for p in plans)
        # tp=1 HYBRID is the replicated canonical when AR is excluded
        assert any(p.tp == 1 for p in plans)

    def test_shortlist_respects_top_k_and_prunes(self):
        ms = MeshSearch(8, parallax.TuneConfig(top_k=2), Plan(1, 8))
        first = ms.begin(_inputs())
        assert ms.started and not ms.done
        assert len(ms._shortlist) == 2
        assert first == ms._shortlist[0]
        s = ms.summary()
        assert s["candidates_enumerated"] == 12
        assert s["pruned_equivalent"] == 5
        assert s["pruned_by_cost_model"] == 5

    def test_bounded_space_accounting_stays_consistent(self):
        """min_tp > 1 keeps AR's canonical tp=1 plan: the enumerated
        count must still cover every scored plan (the decision record
        lands in flight artifacts — 'recorded, never silent')."""
        ms = MeshSearch(8, parallax.TuneConfig(
            run_options=("AR", "SHARD"), min_tp=2), Plan(1, 8, "SHARD"))
        ms.begin(_inputs())
        s = ms.summary()
        scored = len(s["scored"])
        assert scored == 4  # AR@tp1 + SHARD@{2,4,8}
        assert s["candidates_enumerated"] == \
            scored + s["pruned_equivalent"] + 0
        assert s["pruned_equivalent"] >= 0

    def test_report_walks_shortlist_and_picks_measured_argmin(self):
        ms = MeshSearch(8, parallax.TuneConfig(top_k=3), Plan(1, 8))
        plan = ms.begin(_inputs())
        times = iter((0.030, 0.010, 0.020))
        measured = []
        while plan is not None:
            t = next(times)
            measured.append((plan, t))
            plan = ms.report(plan, t)
        assert ms.done
        best = min(measured, key=lambda x: x[1])[0]
        assert ms.best_plan() == best
        s = ms.summary()
        assert s["trials_measured"] == 3 <= s["top_k"]
        w = s["winner"]
        assert w["measured_ms"] == pytest.approx(10.0)
        assert w["predicted_over_measured"] == pytest.approx(
            ms.predicted(best).total_s / 0.010, rel=1e-6)


# -- session seams: plan-aware engine cache -------------------------------


@pytest.fixture
def rng():
    return np.random.default_rng(0)


def _emb_model(V=32, D=8):
    import jax
    import jax.numpy as jnp
    import optax

    from parallax_tpu.ops import embedding as emb_ops

    def init_fn(rng_):
        return {"emb": jax.random.normal(rng_, (V, D)) * 0.1}

    def loss_fn(params, batch):
        rows = emb_ops.embedding_lookup(params["emb"], batch["ids"])
        return jnp.mean(rows ** 2)

    return parallax.Model(init_fn, loss_fn, optimizer=optax.sgd(0.1))


class TestPlanAwareEngineCache:
    def test_same_count_different_plan_gets_distinct_engines(self, rng):
        """ISSUE 10 bugfix pin: equal num_partitions, different mesh
        shape or run option -> distinct engines; exact re-request ->
        cache hit on the same object."""
        sess, *_ = parallax.parallel_run(
            _emb_model(),
            parallax_config=parallax.Config(run_option="HYBRID",
                                            search_partitions=False,
                                            eager_fetch=True),
            num_partitions=2)
        try:
            feed = {"ids": rng.integers(0, 32, (16,)).astype(np.int32)}
            float(sess.run("loss", feed_dict=feed))
            e_hybrid = sess.engine
            assert sess.plan.describe() == "dp4xtp2/HYBRID"
            example = sess._last_example_batch
            builds = sess.metrics.counter("engine.builds").value
            # same device count (8), same shard width, different run
            # option: the old (num_partitions, sig) key collided these
            sess._build_engine(example, Plan(4, 2, "AR"))
            e_ar = sess.engine
            assert e_ar is not e_hybrid
            assert e_ar.config.run_option == consts.RUN_AR
            # different mesh SHAPE at the same run option
            sess._build_engine(example, Plan(2, 4, "HYBRID"))
            e_shape = sess.engine
            assert e_shape is not e_hybrid and e_shape is not e_ar
            assert sess.metrics.counter("engine.builds").value == \
                builds + 2
            # exact re-request of the first plan: a hit, same object,
            # no new build
            hits0 = sess.compile_stats()["engine_cache"]["hits"]
            sess._build_engine(example, Plan(4, 2, "HYBRID"))
            assert sess.engine is e_hybrid
            assert sess.compile_stats()["engine_cache"]["hits"] == \
                hits0 + 1
            assert sess.metrics.counter("engine.builds").value == \
                builds + 2
        finally:
            sess.close()

    def test_legacy_int_key_maps_to_plan(self, rng):
        """The legacy ``_build_engine(example, p)`` call sites (the
        partition search) key through the same plan space."""
        sess, *_ = parallax.parallel_run(
            _emb_model(),
            parallax_config=parallax.Config(run_option="HYBRID",
                                            search_partitions=False,
                                            eager_fetch=True),
            num_partitions=2)
        try:
            feed = {"ids": rng.integers(0, 32, (16,)).astype(np.int32)}
            float(sess.run("loss", feed_dict=feed))
            e0 = sess.engine
            hits0 = sess.compile_stats()["engine_cache"]["hits"]
            sess._build_engine(sess._last_example_batch, 2)
            assert sess.engine is e0
            assert sess.compile_stats()["engine_cache"]["hits"] == \
                hits0 + 1
        finally:
            sess.close()


# -- the measured end-to-end search (subprocess driver) -------------------


def _run_driver_json(cmd, timeout=480.0, attempts=2):
    env = dict(os.environ,
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(os.path.dirname(__file__), "..")]
                   + ([os.environ["PYTHONPATH"]]
                      if os.environ.get("PYTHONPATH") else [])),
               JAX_PLATFORMS="cpu")
    import json
    last = None
    for _ in range(attempts):
        proc = subprocess.run(cmd, env=env, capture_output=True,
                              text=True, timeout=timeout)
        if proc.returncode < 0 or proc.returncode in (134, 139):
            last = (f"driver died with rc={proc.returncode}: "
                    f"{proc.stderr[-500:]}")
            continue
        start = proc.stdout.find("{")
        assert start >= 0, (
            f"driver printed no JSON (rc={proc.returncode}): "
            f"{proc.stdout[-300:]} {proc.stderr[-500:]}")
        result = json.loads(proc.stdout[start:])
        assert proc.returncode == 0, (proc.returncode, result,
                                      proc.stderr[-800:])
        return result
    raise AssertionError(last)


def test_mesh_search_end_to_end_vs_exhaustive():
    """Acceptance (ISSUE 10): on the 8-virtual-device rig MeshSearch
    enumerates the full space, measures at most top-k candidates
    (compile/trial counters), and its winner's measured step time is
    close to the best exhaustively-measured plan; the cost model's
    ranking correlates with the exhaustive measurements."""
    result = _run_driver_json(
        [sys.executable,
         os.path.join(os.path.dirname(__file__),
                      "mesh_search_driver.py")])
    assert result["converged"], result
    s = result["summary"]
    assert s["candidates_enumerated"] == 12
    assert s["trials_measured"] <= s["top_k"]
    # at most one engine build per trial plus the base-plan probe
    assert result["builds"] <= s["top_k"] + 1, result
    # settling on the measured winner never rebuilds: either the
    # winner was the live (last-trialed) engine already, or switching
    # back to it was an engine-cache hit
    if s["winner"]["plan"] != s["trials"][-1]["plan"]:
        assert result["engine_cache"]["hits"] >= 1, result
    assert result["winner_is_measured_candidate"], result
    # Winner quality vs the exhaustive sweep. On real hardware the
    # bar is 10%; on this shared-CPU rig the non-AR plans are
    # genuinely near-tied and re-measuring the SAME plan varies
    # ±30% between windows (measured while building this driver), so
    # the stable assertable property is "never picks a bad plan":
    # within 1.5x of the exhaustive best (AR measures ~3-4x best) and
    # never the model's/measurement's worst. The driver reports the
    # exact ratio into the artifact for trend-watching.
    assert result["winner_over_best"] <= 1.5, result
    worst_plan = max(result["exhaustive"],
                     key=lambda r: r["measured_ms"])["plan"]
    assert result["winner_plan"] != worst_plan, result
    assert result["winner_plan"] != "dp8xtp1/AR", result
    # rank correlation: the model must order the measured plan times
    # (the AR-vs-sparse separation is the load-bearing distinction)
    assert result["n_plans"] >= 3
    assert result["spearman"] >= 0.4, result
    assert result["model_worst_is_measured_worst"], result
    # the pipeline plan pool (ISSUE 18): the same driver measures a
    # pp-bearing pool on a pipeline-capable LM — the bubble + wire
    # pricing must rank the measured pp separations too
    pool = result["pp_pool"]
    assert "error" not in pool, pool
    assert pool["n_plans"] >= 3
    assert any(r["pp"] > 1 for r in pool["rows"]), pool
    assert all(r["bubble_fraction"] is not None
               for r in pool["rows"] if r["pp"] > 1), pool
    assert pool["spearman"] >= 0.4, pool
    # calibration loop (ISSUE 13): ratios derived from a profiled
    # window of the probe plan, persisted + reloaded, must leave the
    # ranking no worse than the nominal constants' on the SAME
    # measured sweep
    assert result["calibration_error"] is None, result
    assert result["calibration"], result
    assert result["spearman_calibrated"] is not None, result
    assert result["spearman_calibrated"] >= result["spearman"], result


def test_flight_dump_carries_tune_record(tmp_path, rng):
    """The tuner's decision record is a flight-recorder provider: a
    post-search dump names the winner and the per-trial
    predicted-vs-measured terms."""
    import json

    sess, *_ = parallax.parallel_run(
        _emb_model(),
        parallax_config=parallax.Config(
            run_option="HYBRID", search_partitions=False,
            eager_fetch=True,
            tune_config=parallax.TuneConfig(
                top_k=1, trial_steps=2, trial_warmup=0,
                run_options=("HYBRID",))))
    try:
        feed = {"ids": rng.integers(0, 32, (16,)).astype(np.int32)}
        for _ in range(4):
            float(sess.run("loss", feed_dict=feed))
            if sess._search is None:
                break
        assert sess._search is None, "top_k=1 search should settle"
        assert sess.tune_summary() is not None
        path = sess.dump_flight(str(tmp_path / "dump.json"))
        doc = json.loads(open(path).read())
        tune = doc["tune"]
        assert tune["winner"]["plan"] == sess.plan.describe()
        assert tune["trials"][0]["predicted_ms"] is not None
        assert tune["trials"][0]["measured_ms"] is not None
    finally:
        sess.close()


# -- the third mesh axis: (dp x tp x pp) plans (ISSUE 18) -----------------


def _pipeline_record(**kw):
    """A model-declared pipeline capability record (what
    ``Model.pipeline_info`` + ``inputs_from_engine`` produce)."""
    rec = dict(schedule="gpipe", microbatches=4, virtual_stages=1,
               pinned_stages=None, num_layers=8, model_dim=32,
               act_itemsize=4, act_bytes=1_000_000, global_batch=32)
    rec.update(kw)
    return rec


class TestPipelineBubbleMath:
    """Hand-computed tick/bubble accounting — the one owner
    (costmodel.pipeline_bubble) both the pricing and the
    wire report consume."""

    def test_gpipe_bubble_hand_computed(self):
        # S=4 stages, M=4 microbatches: ticks = 4 + 3 = 7
        b = costmodel.pipeline_bubble(4, 4)
        assert b["ticks"] == 7
        assert b["bubble_fraction"] == pytest.approx(3 / 7)
        assert b["on_chip_scale"] == pytest.approx(7 / 4)
        # at M % S == 0 the scale is exactly 1/(1 - bubble)
        assert b["on_chip_scale"] == pytest.approx(
            1 / (1 - b["bubble_fraction"]))

    def test_interleaving_cuts_the_bubble(self):
        # V=2 chunks: ticks = 2*4 + 3 = 11, bubble 3/11 < 3/7
        b1 = costmodel.pipeline_bubble(4, 4, virtual_stages=1)
        b2 = costmodel.pipeline_bubble(4, 4, virtual_stages=2)
        assert b2["ticks"] == 11
        assert b2["bubble_fraction"] == pytest.approx(3 / 11)
        assert b2["bubble_fraction"] < b1["bubble_fraction"]
        assert b2["on_chip_scale"] == pytest.approx(11 / 8)

    def test_ragged_interleaved_prices_rounded_microbatches(self):
        # M=6 is ragged over S=4 at V=2: padded to 8 entries/chunk,
        # ticks = 2*8 + 3 = 19 over 12 ideal slots — the masked
        # bubble entries the schedule really executes
        b = costmodel.pipeline_bubble(6, 4, virtual_stages=2)
        assert b["microbatches_scheduled"] == 8
        assert b["ticks"] == 19
        assert b["on_chip_scale"] == pytest.approx(19 / 12)
        # V=1 schedules never round
        assert costmodel.pipeline_bubble(6, 4)[
            "microbatches_scheduled"] == 6

    def test_bubble_refuses_degenerate_inputs(self):
        with pytest.raises(ValueError, match="M, S, V"):
            costmodel.pipeline_bubble(0, 4)
        with pytest.raises(ValueError, match="M, S, V"):
            costmodel.pipeline_bubble(4, 4, virtual_stages=0)

    def test_wire_bytes_hand_computed(self):
        # act 1000 B global, M=4, S=4, dp=2: one hop carries one
        # microbatch of one replica row -> 1000/(4*2) = 125 B; every
        # tick every device ppermutes -> 125 * (2*1*4) * 7 = 7000 B
        g = costmodel.pipeline_wire_bytes(1000.0, 4, 4, dp=2,
                                          schedule="gpipe")
        assert g["per_hop_bytes"] == pytest.approx(125.0)
        assert g["ticks"] == 7
        assert g["activation_bytes"] == pytest.approx(7000.0)
        assert g["cotangent_bytes"] == 0.0
        assert g["total_bytes"] == pytest.approx(7000.0)

    def test_1f1b_cotangent_doubles_the_stream(self):
        g = costmodel.pipeline_wire_bytes(1000.0, 4, 4, dp=2,
                                          schedule="gpipe")
        f = costmodel.pipeline_wire_bytes(1000.0, 4, 4, dp=2,
                                          schedule="1f1b")
        assert f["cotangent_bytes"] == pytest.approx(
            f["activation_bytes"])
        assert f["total_bytes"] == pytest.approx(
            2 * g["total_bytes"])

    def test_balanced_stage_cut_hand_computed(self):
        # symmetric hot ends: the DP finds the even 6/6 split
        cut, sums = costmodel.balanced_stage_cut(
            [4, 1, 1, 1, 1, 4], 2)
        assert cut == [0, 3, 6]
        assert sums == [6.0, 6.0]
        # uniform layers split evenly
        cut, sums = costmodel.balanced_stage_cut([1.0] * 8, 4)
        assert cut == [0, 2, 4, 6, 8]
        assert sums == [2.0] * 4
        # a hot middle layer is isolated with its cheapest neighbors
        cut, sums = costmodel.balanced_stage_cut(
            [1, 1, 5, 1, 1, 1], 2)
        assert cut == [0, 3, 6]
        assert sums == [7.0, 3.0]

    def test_stage_cut_refuses_more_stages_than_layers(self):
        with pytest.raises(ValueError, match="stages"):
            costmodel.balanced_stage_cut([1.0], 2)


class TestPipelinePlanPricing:
    def test_pp_scales_on_chip_and_adds_wire(self):
        base = costmodel.predict(Plan(8, 1, "HYBRID"), _inputs())
        pp = costmodel.predict(
            Plan(4, 1, "HYBRID", pp=2, microbatches=4),
            _inputs(pipeline=_pipeline_record()))
        # S=2, M=4: scale (4+1)/4 = 1.25; uniform layers -> no
        # imbalance penalty
        assert pp.terms["compute_s"] == pytest.approx(
            base.terms["compute_s"] * 1.25)
        assert pp.terms["hbm_s"] == pytest.approx(
            base.terms["hbm_s"] * 1.25)
        want = costmodel.pipeline_wire_bytes(
            1_000_000, 4, 2, dp=4, schedule="gpipe")["total_bytes"]
        assert pp.terms["wire_pp_s"] == pytest.approx(
            want / (8 * 1e10))
        # pp=1 plans never grow pipeline terms — byte-identical 2-D
        # breakdown
        assert "wire_pp_s" not in base.terms
        assert "pp_bubble_s" not in base.terms
        assert base.pipeline is None

    def test_pricing_record_explains_the_cut(self):
        pp = costmodel.predict(
            Plan(4, 1, "HYBRID", pp=2, microbatches=4),
            _inputs(pipeline=_pipeline_record()))
        rec = pp.pipeline
        assert rec["pp"] == 2
        assert rec["bubble_fraction"] == pytest.approx(0.2)
        assert rec["stage_cut"] == [0, 4, 8]  # 8 uniform layers
        assert rec["imbalance"] == pytest.approx(1.0)
        d = pp.as_dict()
        assert d["pp"] == 2
        assert d["pipeline"]["stage_cut"] == [0, 4, 8]

    def test_declared_layer_costs_scale_the_imbalance(self):
        plan = Plan(4, 1, "HYBRID", pp=2, microbatches=4)
        even = costmodel.predict(
            plan, _inputs(pipeline=_pipeline_record(num_layers=6)))
        hot = costmodel.predict(
            plan, _inputs(pipeline=_pipeline_record(
                num_layers=6, layer_costs=[1, 1, 5, 1, 1, 1])))
        # cut [1,1,5 | 1,1,1]: imbalance = 2 * 7 / 10 = 1.4
        assert hot.pipeline["imbalance"] == pytest.approx(1.4)
        assert hot.terms["compute_s"] == pytest.approx(
            even.terms["compute_s"] * 1.4)

    def test_1f1b_schedule_doubles_pp_wire(self):
        plan = Plan(4, 1, "HYBRID", pp=2, microbatches=4)
        g = costmodel.predict(
            plan, _inputs(pipeline=_pipeline_record()))
        f = costmodel.predict(
            plan, _inputs(pipeline=_pipeline_record(schedule="1f1b")))
        assert f.terms["wire_pp_s"] == pytest.approx(
            2 * g.terms["wire_pp_s"])

    def test_pp_without_pipeline_record_refuses(self):
        with pytest.raises(ValueError, match="pipeline"):
            costmodel.predict(Plan(4, 1, "HYBRID", pp=2), _inputs())

    def test_calibration_folds_pp_wire_into_wire_term(self):
        from parallax_tpu.tune import calibrate
        pp = costmodel.predict(
            Plan(4, 1, "HYBRID", pp=2, microbatches=4),
            _inputs(pipeline=_pipeline_record()))
        terms = calibrate.predicted_terms_from_cost(pp.terms)
        wire_wo = calibrate.predicted_terms_from_cost(
            {k: v for k, v in pp.terms.items() if k != "wire_pp_s"})
        assert terms["wire"] == pytest.approx(
            wire_wo["wire"] + pp.terms["wire_pp_s"])


class TestPipelinePlanValidation:
    def test_plan_refuses_nonpositive_pp(self):
        with pytest.raises(ValueError, match="pp"):
            Plan(1, 8, pp=0)

    def test_plan_product_covers_all_three_axes(self):
        with pytest.raises(ValueError, match="dp\\*tp\\*pp"):
            Plan(4, 1, "HYBRID", pp=2).validate_for(4)
        Plan(4, 1, "HYBRID", pp=2).validate_for(8)  # ok

    def test_schedule_knobs_require_pp(self):
        with pytest.raises(ValueError, match="pp > 1"):
            Plan(1, 8, virtual_stages=2)
        with pytest.raises(ValueError, match="pp > 1"):
            Plan(1, 8, microbatches=4)

    def test_mesh_shape_is_legacy_2_tuple_at_pp1(self):
        assert Plan(8, 1).mesh_shape() == (8, 1)
        assert Plan(4, 1, "HYBRID", pp=2).mesh_shape() == (4, 1, 2)

    def test_describe_and_cache_key_distinguish_pp(self):
        assert Plan(8, 1, "HYBRID").describe() == "dp8xtp1/HYBRID"
        p = Plan(4, 1, "HYBRID", pp=2, microbatches=4)
        assert p.describe() == "dp4xtp1xpp2/HYBRID+m4"
        v = Plan(4, 1, "HYBRID", pp=2, virtual_stages=2,
                 microbatches=4)
        assert v.describe() == "dp4xtp1xpp2/HYBRID+v2+m4"
        keys = {Plan(8, 1, "HYBRID").cache_key(), p.cache_key(),
                v.cache_key()}
        assert len(keys) == 3

    def test_tune_config_refuses_bad_max_pp(self):
        with pytest.raises(ValueError, match="max_pp"):
            parallax.TuneConfig(max_pp=0)


class TestPipelineEnumeration:
    def test_pp1_block_is_byte_identical_to_2d_space(self):
        """The load-bearing zero-behavior-change pin: with the pp
        dimension open, the pp=1 sub-list is EXACTLY yesterday's 2-D
        list, element for element."""
        with_pp = emittable_plans(8, max_pp=8,
                                  pipeline=_pipeline_record())
        assert [p for p in with_pp if p.pp == 1] == emittable_plans(8)
        full = enumerate_plans(8, max_pp=8,
                               pipeline=_pipeline_record())
        assert [p for p in full if p.pp == 1] == enumerate_plans(8)

    def test_max_pp_without_capability_record_is_a_noop(self):
        assert emittable_plans(8, max_pp=8) == emittable_plans(8)
        assert enumerate_plans(8, max_pp=8) == enumerate_plans(8)

    def test_pp_values_respect_divisibility(self):
        # 8 devices, 8 layers: pp in {2, 4, 8} all divide both; a
        # 6-layer model excludes pp=4 and pp=8 (stage reshape ragged)
        plans = emittable_plans(8, max_pp=8,
                                pipeline=_pipeline_record())
        assert {p.pp for p in plans} == {1, 2, 4, 8}
        plans6 = emittable_plans(
            8, max_pp=8, pipeline=_pipeline_record(num_layers=6))
        assert {p.pp for p in plans6} == {1, 2}

    def test_max_pp_caps_the_lattice(self):
        plans = emittable_plans(8, max_pp=2,
                                pipeline=_pipeline_record())
        assert {p.pp for p in plans} == {1, 2}

    def test_pinned_stages_pin_pp_under_interleaving(self):
        # a V>1 storage order is baked for one stage count: only that
        # pp enumerates
        plans = emittable_plans(8, max_pp=8, pipeline=_pipeline_record(
            virtual_stages=2, pinned_stages=2))
        assert {p.pp for p in plans} == {1, 2}
        assert all(p.virtual_stages == 2
                   for p in plans if p.pp > 1)

    def test_microbatch_divisibility_prunes_inadmissible_dp(self):
        # global_batch=4, M=4: dp must satisfy (4/dp) % 4 == 0 -> only
        # dp=1 survives per pp block
        plans = emittable_plans(
            8, max_pp=2, pipeline=_pipeline_record(global_batch=4))
        assert all(p.dp == 1 for p in plans if p.pp > 1)

    def test_each_pp_block_keeps_one_replicated_canonical(self):
        plans = emittable_plans(8, max_pp=8,
                                pipeline=_pipeline_record())
        for pp in (1, 2, 4, 8):
            tp1 = [p for p in plans if p.pp == pp and p.tp == 1]
            assert len(tp1) == 1, (pp, tp1)

    def test_search_summary_reports_pp_gate_state(self):
        ms = MeshSearch(8, parallax.TuneConfig(top_k=2, max_pp=4),
                        Plan(1, 8))
        ms.begin(_inputs(pipeline=_pipeline_record()))
        s = ms.summary()
        assert s["max_pp"] == 4
        assert s["pipeline_capable"] is True
        assert any(pc["pp"] > 1 for pc in s["scored"])
        # without the record the same config stays 2-D and says so
        ms2 = MeshSearch(8, parallax.TuneConfig(top_k=2, max_pp=4),
                         Plan(1, 8))
        ms2.begin(_inputs())
        s2 = ms2.summary()
        assert s2["pipeline_capable"] is False
        assert all(pc["pp"] == 1 for pc in s2["scored"])


def _pipeline_lc_model(num_layers=4, microbatches=2,
                       schedule="gpipe"):
    import jax.numpy as jnp

    from parallax_tpu.models import long_context as lc

    cfg = lc.tiny_config(parallelism="pipeline",
                         num_layers=num_layers,
                         num_microbatches=microbatches,
                         pipeline_schedule=schedule,
                         compute_dtype=jnp.float32)
    return lc.build_model(cfg), cfg


class TestPipelineEngineCache:
    def test_pp_plan_keys_apart_and_routes_to_3_axis_mesh(self, rng):
        """ISSUE 18 cache pin (same shape as the ISSUE 10 one): a pp
        plan must never collide with its 2-D peer — the key carries
        the full 3-tuple + schedule knobs — and the pp engine really
        runs on a 3-axis mesh."""
        from parallax_tpu.core import mesh as mesh_lib
        from parallax_tpu.models import long_context as lc

        model, cfg = _pipeline_lc_model()
        sess, *_ = parallax.parallel_run(
            model,
            parallax_config=parallax.Config(run_option="HYBRID",
                                            search_partitions=False,
                                            eager_fetch=True),
            num_partitions=1)
        try:
            feed = lc.make_batch(rng, 8, 16, cfg.vocab_size)
            float(sess.run("loss", feed_dict=feed))
            e_flat = sess.engine
            assert sess.plan.describe() == "dp8xtp1/HYBRID"
            assert mesh_lib.AXIS_PIPE not in e_flat.mesh.axis_names
            example = sess._last_example_batch
            builds = sess.metrics.counter("engine.builds").value
            pp_plan = Plan(4, 1, "HYBRID", pp=2, microbatches=2)
            sess._build_engine(example, pp_plan)
            e_pp = sess.engine
            assert e_pp is not e_flat
            assert mesh_lib.AXIS_PIPE in e_pp.mesh.axis_names
            assert dict(zip(e_pp.mesh.axis_names,
                            e_pp.mesh.devices.shape)) == {
                "repl": 4, "shard": 1, "pipe": 2}
            assert sess.metrics.counter("engine.builds").value == \
                builds + 1
            # exact re-request of either plan: cache hits, no build
            hits0 = sess.compile_stats()["engine_cache"]["hits"]
            sess._build_engine(example, Plan(8, 1, "HYBRID"))
            assert sess.engine is e_flat
            sess._build_engine(example, pp_plan)
            assert sess.engine is e_pp
            assert sess.compile_stats()["engine_cache"]["hits"] == \
                hits0 + 2
            assert sess.metrics.counter("engine.builds").value == \
                builds + 1
        finally:
            sess.close()


def test_oom_unlock_pp_plan_survives_preflight():
    """The PR's headline proof (ISSUE 18): a model whose compiled
    peak REFUSES every 2-D plan still trains — the preflight
    backfills the shortlist from the 3-D lattice and a pp>1 plan
    wins, with the refusal, the stage cut and the bubble all in the
    decision record. Runs in an isolated driver process
    (tests/oom_unlock_driver.py): an in-process multi-mesh search is
    exactly the workload that intermittently hard-crashes this
    XLA:CPU toolchain — isolation makes a crash cost one retry,
    never the pytest process."""
    r = _run_driver_json(
        [sys.executable,
         os.path.join(os.path.dirname(__file__),
                      "oom_unlock_driver.py")])
    assert r["settled"], "search should settle"
    # the whole 2-D space (one replicated AR plan) was refused...
    assert r["pruned_oom"] >= 1, r
    assert "dp8xtp1/AR" in r["refused"], r
    # ...and the winner is a pipeline plan that could not have been
    # emitted before the third axis existed
    assert r["winner"]["pp"] > 1, r["winner"]
    assert r["winner"]["plan"] not in r["refused"]
    assert r["winner"]["bubble_fraction"] is not None
    assert r["session_plan_pp"] > 1
    assert "pipe" in r["mesh_axes"]
    # the scored record explains the cut
    assert r["winner_stage_cut"] is not None
    assert r["winner_wire_pp_s"] is not None
    # the proof rides the tune_decision flight artifact
    assert r["artifact_pruned_oom"] >= 1
    assert r["artifact_winner_pp"] > 1
