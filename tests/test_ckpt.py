"""Checkpoint/recovery subsystem tests (ISSUE 9, parallax_tpu/ckpt).

Covers: the atomic store's integrity guarantees (checksums, torn
detection, fallback, GC), exact resume (bit-identical losses through
the data-cursor replay protocol), resharded restore (save on one
partition layout, continue on another), NaN auto-rollback with bounded
retries, async-save promotion + validation (the old silent getattr
probe), SIGTERM preemption handling, and the subprocess chaos guard
(tools/check_train_faults.py: SIGKILL mid-step, crash mid-save,
injected NaN — the ISSUE 9 acceptance contract).
"""

import glob
import os
import signal
import subprocess
import sys

import numpy as np
import pytest

import parallax_tpu as parallax
from parallax_tpu.ckpt import (CheckpointCorrupt, CheckpointStore,
                               CheckpointTreeMismatch,
                               RecoverySurrender)
from parallax_tpu.ckpt.hook import CheckpointHook
from parallax_tpu.models import simple


def batch_for(i, nan=False):
    b = simple.make_batch(np.random.default_rng(4000 + i), 32)
    if nan:
        b["x"] = b["x"] * np.nan
    return b


def _cfg(ckpt_dir=None, every=3, **ckpt_kw):
    return parallax.Config(
        run_option="AR", search_partitions=False,
        ckpt_config=parallax.CheckPointConfig(
            ckpt_dir=ckpt_dir, save_ckpt_steps=every, **ckpt_kw))


def _train(cfg, n, start=0, losses=None):
    sess, *_ = parallax.parallel_run(simple.build_model(0.1),
                                     parallax_config=cfg)
    got = sess.prepare(batch_for(0))
    assert got == start
    out = []
    for i in range(got, n):
        out.append(float(sess.run("loss", feed_dict=batch_for(i))))
    if losses is not None:
        losses.extend(out)
    return sess


# ---------------------------------------------------------------------------
# store units
# ---------------------------------------------------------------------------

class TestStore:
    def _state(self):
        """A sharded pytree exercising replicated + row-sharded +
        bf16 + scalar leaves."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import (Mesh, NamedSharding,
                                  PartitionSpec as P)
        mesh = Mesh(np.array(jax.devices()).reshape(2, 4),
                    ("repl", "shard"))
        return {
            "table": jax.device_put(
                np.arange(64, dtype=np.float32).reshape(8, 8),
                NamedSharding(mesh, P("shard", None))),
            "dense": jax.device_put(
                np.linspace(0, 1, 12, dtype=np.float32).reshape(3, 4),
                NamedSharding(mesh, P())),
            "bf16": jax.device_put(
                jnp.asarray(np.arange(6), jnp.bfloat16),
                NamedSharding(mesh, P())),
            "step": jax.device_put(jnp.int32(7),
                                   NamedSharding(mesh, P())),
        }

    def test_roundtrip_bit_identical(self, tmp_path):
        state = self._state()
        store = CheckpointStore(str(tmp_path / "s"))
        store.save(5, state, extras={"cursor": 5})
        out = store.restore_latest(state)
        assert out is not None
        restored, step, info = out
        assert step == 5 and not info["fallbacks"]
        assert store.restore_extras(5) == {"cursor": 5}
        for k in state:
            a, b = np.asarray(state[k]), np.asarray(restored[k])
            assert a.dtype == b.dtype
            assert np.array_equal(a, b), k
            # shardings survive too
            assert restored[k].sharding == state[k].sharding, k

    def test_truncated_shard_falls_back(self, tmp_path):
        state = self._state()
        store = CheckpointStore(str(tmp_path / "s"))
        store.save(2, state)
        store.save(4, state)
        f = glob.glob(str(tmp_path / "s" / "4" / "shards_*.npz"))[0]
        with open(f, "r+b") as fh:
            fh.truncate(16)
        restored, step, info = store.restore_latest(state)
        assert step == 2
        assert [k["step"] for k in info["fallbacks"]] == [4]

    def test_checksum_mismatch_falls_back(self, tmp_path):
        import json
        state = self._state()
        store = CheckpointStore(str(tmp_path / "s"))
        store.save(2, state)
        store.save(4, state)
        # corrupt a recorded checksum: the bytes no longer match
        mpath = str(tmp_path / "s" / "4" / "manifest.json")
        m = json.load(open(mpath))
        row = m["leaves"]["table"]["shards"][0]
        row["crc32"] = (row["crc32"] + 1) & 0xFFFFFFFF
        json.dump(m, open(mpath, "w"))
        with pytest.raises(CheckpointCorrupt):
            store.restore(4, state)
        _, step, info = store.restore_latest(state)
        assert step == 2 and info["fallbacks"]

    def test_missing_manifest_is_torn(self, tmp_path):
        state = self._state()
        store = CheckpointStore(str(tmp_path / "s"))
        store.save(2, state)
        store.save(4, state)
        os.remove(str(tmp_path / "s" / "4" / "manifest.json"))
        assert store.complete_steps() == [2]
        _, step, info = store.restore_latest(state)
        assert step == 2 and info["torn_steps"] == [4]

    def test_mid_write_crash_leaves_restorable_previous(self, tmp_path):
        """In-process 'crash mid-save': the fault hook raises after the
        shard files land but before the manifest commit — the previous
        complete checkpoint must restore untouched."""
        state = self._state()
        store = CheckpointStore(str(tmp_path / "s"))
        store.save(2, state)

        def die(phase):
            if phase == "before_manifest":
                raise OSError("simulated crash mid-commit")

        store._fault_hook = die
        with pytest.raises(OSError):
            store.save(4, state)
        store._fault_hook = None
        assert store.complete_steps() == [2]  # 4 is torn, 2 intact
        restored, step, _ = store.restore_latest(state)
        assert step == 2
        assert np.array_equal(np.asarray(restored["table"]),
                              np.asarray(state["table"]))

    def test_template_shape_mismatch_refuses(self, tmp_path):
        state = self._state()
        store = CheckpointStore(str(tmp_path / "s"))
        store.save(1, state)
        bad = dict(state, dense=np.zeros((5, 4), np.float32))
        with pytest.raises(CheckpointTreeMismatch, match="shape"):
            store.restore(1, bad)

    def test_tree_mismatch_is_two_way_and_propagates(self, tmp_path):
        """A template that would silently DROP saved leaves (e.g.
        sync=False checkpoint restored by a sync=True template) is a
        config mismatch: restore refuses in both directions, and
        restore_latest PROPAGATES instead of degrading to a fresh
        start via fallback (older checkpoints share the structure)."""
        state = self._state()
        store = CheckpointStore(str(tmp_path / "s"))
        store.save(2, state)
        store.save(4, state)
        subset = {k: v for k, v in state.items() if k != "bf16"}
        with pytest.raises(CheckpointTreeMismatch,
                           match="absent from template"):
            store.restore(4, subset)
        with pytest.raises(CheckpointTreeMismatch):
            store.restore_latest(subset)
        superset = dict(state, extra=np.zeros((2,), np.float32))
        with pytest.raises(CheckpointTreeMismatch,
                           match="missing from checkpoint"):
            store.restore(4, superset)

    def test_dtype_mismatch_refuses(self, tmp_path):
        """A precision change between save and resume (bf16 -> f32
        params, same shapes) must refuse loudly, not hand the AOT step
        arrays off its compiled signature."""
        import jax.numpy as jnp
        state = self._state()
        store = CheckpointStore(str(tmp_path / "s"))
        store.save(1, state)
        bad = dict(state,
                   bf16=np.zeros((6,), np.float32))  # was bfloat16
        with pytest.raises(CheckpointTreeMismatch, match="dtype"):
            store.restore(1, bad)
        del jnp

    def test_resave_clears_stale_process_shards(self, tmp_path):
        """Re-saving a step over a COMMITTED checkpoint (NaN-rollback
        rewind, fallback retrain) must clear stale shards_<p>.* from a
        previous (e.g. wider) run, or _merge_manifest would fold dead
        bytes into the fresh manifest."""
        import shutil as sh
        state = self._state()
        store = CheckpointStore(str(tmp_path / "s"))
        store.save(8, state)
        d = str(tmp_path / "s" / "8")
        # simulate a dead second process's leftovers
        sh.copy(os.path.join(d, "shards_0.npz"),
                os.path.join(d, "shards_1.npz"))
        sh.copy(os.path.join(d, "shards_0.json"),
                os.path.join(d, "shards_1.json"))
        store.save(8, state)  # re-save same step
        import json
        m = json.load(open(os.path.join(d, "manifest.json")))
        files = {row["file"] for e in m["leaves"].values()
                 for row in e["shards"]}
        assert files == {"shards_0.npz"}
        restored, step, _ = store.restore_latest(state)
        assert step == 8
        np.testing.assert_array_equal(np.asarray(restored["table"]),
                                      np.asarray(state["table"]))

    def test_save_refuses_foreign_step_dir(self, tmp_path):
        state = self._state()
        store = CheckpointStore(str(tmp_path / "s"))
        legacy = tmp_path / "s" / "3"
        legacy.mkdir(parents=True)
        (legacy / "_CHECKPOINT_METADATA").write_text("{}")
        with pytest.raises(CheckpointCorrupt, match="pre-upgrade"):
            store.save(3, state)
        assert (legacy / "_CHECKPOINT_METADATA").exists()

    def test_foreign_layout_never_deleted(self, tmp_path, caplog):
        """A numeric step dir in an UNRECOGNIZED on-disk layout (a
        pre-upgrade orbax checkpoint) must survive GC and restore
        scans untouched, with a loud log — never silently destroyed
        as 'torn'."""
        import logging
        state = self._state()
        store = CheckpointStore(str(tmp_path / "s"), max_to_keep=1)
        legacy = tmp_path / "s" / "1"
        legacy.mkdir()
        (legacy / "_CHECKPOINT_METADATA").write_text("{}")
        (legacy / "array_store").mkdir()
        with caplog.at_level(logging.ERROR):
            store.save(5, state)
            store.save(6, state)          # GC pass runs here
            out = store.restore_latest(state)
        assert out is not None and out[1] == 6
        assert legacy.is_dir()            # survived both GC passes
        assert any("UNRECOGNIZED layout" in r.message
                   for r in caplog.records)

    def test_gc_retention_and_torn_cleanup(self, tmp_path):
        state = self._state()
        store = CheckpointStore(str(tmp_path / "s"), max_to_keep=2)
        for s in (1, 2, 3):
            store.save(s, state)
        assert store.complete_steps() == [2, 3]
        # an old torn dir (older than the newest complete) is removed
        os.makedirs(str(tmp_path / "s" / "0"))
        store.gc()
        assert not os.path.isdir(str(tmp_path / "s" / "0"))
        # keep-everything opt-out
        store2 = CheckpointStore(str(tmp_path / "s2"),
                                 max_to_keep=None)
        for s in (1, 2, 3, 4):
            store2.save(s, state)
        assert store2.complete_steps() == [1, 2, 3, 4]


# ---------------------------------------------------------------------------
# config promotion (satellite 1)
# ---------------------------------------------------------------------------

class TestConfigValidation:
    def test_misspelled_async_knob_raises(self):
        # the old getattr probe silently defaulted off on a typo; the
        # dataclass field rejects unknown kwargs at construction
        with pytest.raises(TypeError):
            parallax.CheckPointConfig(asycn_save=True)

    def test_async_save_must_be_bool(self):
        with pytest.raises(ValueError, match="async_save"):
            parallax.CheckPointConfig(async_save="yes")

    def test_trigger_and_retention_validation(self):
        with pytest.raises(ValueError, match="save_ckpt_steps"):
            parallax.CheckPointConfig(save_ckpt_steps=0)
        with pytest.raises(ValueError, match="save_ckpt_secs"):
            parallax.CheckPointConfig(save_ckpt_secs=0)
        with pytest.raises(ValueError, match="max_to_keep"):
            parallax.CheckPointConfig(max_to_keep=0)
        assert parallax.CheckPointConfig(max_to_keep=None) \
            .max_to_keep is None

    def test_recovery_config_validation(self):
        with pytest.raises(ValueError, match="snapshot_every_steps"):
            parallax.RecoveryConfig(enabled=True,
                                    snapshot_every_steps=0)
        with pytest.raises(ValueError, match="max_retries"):
            parallax.RecoveryConfig(max_retries=0)

    def test_recovery_auto_enables_monitor_health(self):
        cfg = parallax.Config(
            recovery_config=parallax.RecoveryConfig(enabled=True))
        assert cfg.monitor_health

    def test_async_save_honored_not_getattr(self, tmp_path):
        """The hook reads the declared field: async_save=True routes
        saves through the background writer and commits by close()."""
        hook = CheckpointHook(
            parallax.CheckPointConfig(ckpt_dir=str(tmp_path / "c"),
                                      save_ckpt_steps=1,
                                      async_save=True),
            worker_id=0)
        state = {"w": np.ones((4,), np.float32)}
        assert hook.maybe_save(1, state)
        hook.close()  # joins the writer
        assert CheckpointStore(str(tmp_path / "c")).complete_steps() \
            == [1]

    def test_save_now_dedupes_current_step(self, tmp_path):
        hook = CheckpointHook(
            parallax.CheckPointConfig(ckpt_dir=str(tmp_path / "c"),
                                      save_ckpt_steps=1),
            worker_id=0)
        state = {"w": np.ones((4,), np.float32)}
        assert hook.save_now(3, state, reason="preemption") is not None
        assert hook.save_now(3, state, reason="preemption") is None
        hook.close()

    def test_save_now_refuses_multiprocess(self, tmp_path,
                                           monkeypatch):
        """A signal-path save cannot agree on a step across hosts, and
        an unmatched commit barrier would hang the eviction grace —
        save_now must refuse (loudly) rather than deadlock."""
        import jax
        hook = CheckpointHook(
            parallax.CheckPointConfig(ckpt_dir=str(tmp_path / "c"),
                                      save_ckpt_steps=1),
            worker_id=0)
        monkeypatch.setattr(jax, "process_count", lambda: 2)
        state = {"w": np.ones((4,), np.float32)}
        assert hook.save_now(3, state, reason="preemption") is None
        monkeypatch.undo()
        hook.close()
        assert CheckpointStore(str(tmp_path / "c")).complete_steps() \
            == []


# ---------------------------------------------------------------------------
# exact resume (tentpole part 1)
# ---------------------------------------------------------------------------

class TestExactResume:
    def test_resume_is_bit_identical(self, tmp_path):
        """N uninterrupted steps vs k steps -> abandon (the in-process
        crash stand-in; the SIGKILL variant runs in the subprocess
        chaos guard) -> restore -> N-k steps: bit-identical losses,
        via the run_iter(skip=...) cursor protocol."""
        N = 8
        ref = []
        sess = _train(_cfg(str(tmp_path / "unused")), N, losses=ref)
        sess.close()

        ck = str(tmp_path / "ck")
        sess = _train(_cfg(ck), 5)  # checkpoint committed at step 3
        del sess  # crash stand-in: no close, no final save

        sess2, *_ = parallax.parallel_run(simple.build_model(0.1),
                                          parallax_config=_cfg(ck))
        start = sess2.prepare(batch_for(0))
        assert start == 3 and sess2.data_cursor == 3
        feed = (batch_for(i) for i in range(N))
        got = [float(v) for v in
               sess2.run_iter(feed, fetches="loss", skip="auto")]
        assert got == ref[start:], "resumed losses are not bit-identical"
        sess2.close()

    def test_restore_reports_resume_artifact_and_extras(self, tmp_path):
        ck = str(tmp_path / "ck")
        fdir = str(tmp_path / "flight")
        cfg = _cfg(ck, every=2)
        cfg.monitor_health = True
        sess, *_ = parallax.parallel_run(simple.build_model(0.1),
                                         parallax_config=cfg)
        for i in range(4):
            sess.run("loss", feed_dict=batch_for(i))
        # detector baselines exist by now and ride in the extras
        assert sess.anomaly.snapshot()
        sess.close()

        cfg2 = _cfg(ck, every=2)
        cfg2.monitor_health = True
        cfg2.flight_dir = fdir
        sess2, *_ = parallax.parallel_run(simple.build_model(0.1),
                                          parallax_config=cfg2)
        assert sess2.prepare(batch_for(0)) == 4
        # anomaly baselines restored, not relearned
        snap = sess2.anomaly.snapshot()
        assert snap.get("step_time_ms", {}).get("n", 0) >= 4
        assert any("flight_resume_" in os.path.basename(p)
                   for p in glob.glob(os.path.join(fdir, "*")))
        sess2.close()

    def test_skip_items_protocol(self):
        from parallax_tpu.data.prefetch import Prefetcher, skip_items
        it = skip_items(iter(range(10)), 4)
        assert list(it) == [4, 5, 6, 7, 8, 9]
        with pytest.raises(ValueError, match="cursor"):
            skip_items(iter(range(3)), 5)
        p = Prefetcher(iter(range(6)), lambda x: x * 10, skip=2)
        assert list(p) == [20, 30, 40, 50]

    def test_skip_auto_before_engine_refuses(self):
        """skip='auto' before the restore has happened would resolve
        to cursor 0 and silently retrain the consumed prefix — it must
        refuse and point at prepare()."""
        sess, *_ = parallax.parallel_run(
            simple.build_model(0.1),
            parallax_config=parallax.Config(run_option="AR",
                                            search_partitions=False))
        with pytest.raises(ValueError, match="prepare"):
            sess.run_iter(iter([]), fetches="loss", skip="auto")
        sess.close()

    def test_torn_newest_falls_back_with_loud_artifact(self, tmp_path,
                                                       caplog):
        """Session-level torn restore: the newest checkpoint's shard
        is truncated -> restore falls back to the previous one, logs
        loudly, and leaves a ckpt_torn flight artifact."""
        import logging
        ck = str(tmp_path / "ck")
        sess = _train(_cfg(ck, every=2), 4)  # ckpts at 2, 4
        sess.close()
        f = glob.glob(os.path.join(ck, "4", "shards_*.npz"))[0]
        with open(f, "r+b") as fh:
            fh.truncate(10)
        cfg = _cfg(ck, every=2)
        cfg.flight_dir = str(tmp_path / "flight")
        sess2, *_ = parallax.parallel_run(simple.build_model(0.1),
                                          parallax_config=cfg)
        with caplog.at_level(logging.WARNING):
            assert sess2.prepare(batch_for(0)) == 2
        assert any("FAILED verification" in r.message
                   or "FELL BACK" in r.message
                   for r in caplog.records)
        assert any("ckpt_torn" in os.path.basename(p)
                   for p in glob.glob(cfg.flight_dir + "/*"))
        sess2.close()


# ---------------------------------------------------------------------------
# resharded restore (tentpole part 3)
# ---------------------------------------------------------------------------

def _embed_model():
    """Deterministic-training embedding model for cross-layout loss
    comparison: no jax.random inside the loss, UNIQUE ids per batch
    (duplicate ids would make the table-grad scatter-add's reduction
    order observable — this XLA:CPU toolchain reorders it with process
    conditions), and sgd rather than adam (whose early-step
    normalization amplifies ULP differences into divergent
    trajectories). Continuations across partition layouts then differ
    only by collective reduction order."""
    import jax
    import jax.numpy as jnp
    import optax
    from parallax_tpu.ops import embedding as emb_ops

    V, D = 64, 16

    def init_fn(rng):
        k1, k2 = jax.random.split(rng)
        return {"emb": jax.random.normal(k1, (V, D)) * 0.1,
                "w": jax.random.normal(k2, (D,)) * 0.1}

    def loss_fn(params, batch):
        rows = emb_ops.embedding_lookup(params["emb"], batch["ids"])
        return jnp.mean((rows @ params["w"] - batch["y"]) ** 2)

    def mk():
        return parallax.Model(init_fn, loss_fn,
                              optimizer=optax.sgd(0.1))

    def bf(i):
        r = np.random.default_rng(500 + i)
        return {"ids": r.permutation(V)[:16].astype(np.int32),
                "y": r.standard_normal(16).astype(np.float32)}

    return mk, bf


class TestReshardedRestore:
    def test_restore_onto_other_partition_counts(self, tmp_path):
        """Save on p=8, restore and CONTINUE on p=4 and p=1: losses
        match the same-layout continuation (documented tolerance
        rtol=1e-5; bit-equal here on CPU f32)."""
        mk, bf = _embed_model()
        ck = str(tmp_path / "ck")

        def mkcfg(every=2):
            return parallax.Config(
                run_option="HYBRID", search_partitions=False,
                ckpt_config=parallax.CheckPointConfig(
                    ckpt_dir=ck, save_ckpt_steps=every))

        sess, *_ = parallax.parallel_run(mk(), parallax_config=mkcfg(),
                                         num_partitions=8)
        for i in range(4):
            sess.run("loss", feed_dict=bf(i))
        sess.close()

        def continuation(p):
            s, *_ = parallax.parallel_run(
                mk(), parallax_config=mkcfg(every=10 ** 6),
                num_partitions=p)
            assert s.prepare(bf(0)) == 4
            out = [float(s.run("loss", feed_dict=bf(i)))
                   for i in range(4, 8)]
            s.close()
            return out

        cont = continuation(8)     # same layout: the reference
        got4 = continuation(4)     # fewer partitions (survivor-style)
        got1 = continuation(1)     # fully replicated (serve handoff)
        np.testing.assert_allclose(cont, got4, rtol=1e-5)
        np.testing.assert_allclose(cont, got1, rtol=1e-5)

    def test_eval_flow_restore_across_layouts(self, tmp_path):
        """restore_train_state: the same checkpoint lands replicated
        (no example_batch) and onto a live plan — the store's manifest
        is layout-free."""
        from parallax_tpu.checkpoint import restore_train_state
        mk, bf = _embed_model()
        ck = str(tmp_path / "ck")
        cfg = parallax.Config(
            run_option="HYBRID", search_partitions=False,
            ckpt_config=parallax.CheckPointConfig(ckpt_dir=ck,
                                                  save_ckpt_steps=2))
        sess, *_ = parallax.parallel_run(mk(), parallax_config=cfg,
                                         num_partitions=8)
        for i in range(2):
            sess.run("loss", feed_dict=bf(i))
        want = np.asarray(sess.state.params["emb"])
        sess.close()
        restored, step = restore_train_state(ck, mk())
        assert step == 2
        assert restored.params["emb"].sharding.is_fully_replicated
        np.testing.assert_array_equal(
            np.asarray(restored.params["emb"]), want)


# ---------------------------------------------------------------------------
# NaN auto-recovery (tentpole part 4)
# ---------------------------------------------------------------------------

class TestRecovery:
    def _cfg(self, max_retries=2):
        return parallax.Config(
            run_option="AR", search_partitions=False,
            recovery_config=parallax.RecoveryConfig(
                enabled=True, snapshot_every_steps=2,
                max_retries=max_retries))

    def test_rollback_skips_batch_and_continues(self, tmp_path):
        sess, *_ = parallax.parallel_run(simple.build_model(0.1),
                                         parallax_config=self._cfg())
        calls = []
        sess.set_rollback_hook(calls.append)
        losses = [float(sess.run("loss",
                                 feed_dict=batch_for(i, nan=(i == 5))))
                  for i in range(10)]
        assert sess._recovery.total_rollbacks == 1
        assert calls == [1]
        assert np.isfinite(losses[-1])
        # the cursor counted every batch; the step counter rewound to
        # the snapshot (step 4) and re-advanced over batches 6..9
        assert sess.data_cursor == 10
        assert sess._host_step == 8
        # health accounting still saw the non-finite step
        assert not sess.health.healthy
        sess.close()

    def test_surrender_after_bounded_retries(self, tmp_path):
        fdir = str(tmp_path / "flight")
        cfg = self._cfg(max_retries=2)
        cfg.flight_dir = fdir
        sess, *_ = parallax.parallel_run(simple.build_model(0.1),
                                         parallax_config=cfg)
        with pytest.raises(RecoverySurrender):
            for i in range(10):
                sess.run("loss", feed_dict=batch_for(i, nan=True))
        # max_retries rollbacks happened, then the budget tripped
        assert sess._recovery.total_rollbacks == 2
        classes = {os.path.basename(p) for p in glob.glob(fdir + "/*")}
        assert any("nonfinite_rollback" in c for c in classes)
        assert any("recovery_surrender" in c for c in classes)
        sess.close()


# ---------------------------------------------------------------------------
# preemption (satellite 2)
# ---------------------------------------------------------------------------

class TestPreemption:
    def test_on_preemption_dumps_and_saves(self, tmp_path):
        fdir = str(tmp_path / "flight")
        ck = str(tmp_path / "ck")
        cfg = _cfg(ck, every=100)
        cfg.flight_dir = fdir
        sess, *_ = parallax.parallel_run(simple.build_model(0.1),
                                         parallax_config=cfg)
        assert sess._sigterm_installed
        prev = sess._prev_sigterm
        for i in range(3):
            sess.run("loss", feed_dict=batch_for(i))
        sess.on_preemption(signal.SIGTERM)
        assert any("preemption" in os.path.basename(p)
                   for p in glob.glob(fdir + "/*"))
        # one final out-of-cadence checkpoint at the current step
        assert CheckpointStore(ck).complete_steps() == [3]
        sess.close()
        # close() restored the previous SIGTERM disposition
        assert signal.getsignal(signal.SIGTERM) in (
            prev, signal.SIG_DFL)

    def test_handler_not_installed_without_targets(self):
        cfg = parallax.Config(run_option="AR",
                              search_partitions=False)
        sess, *_ = parallax.parallel_run(simple.build_model(0.1),
                                         parallax_config=cfg)
        assert not sess._sigterm_installed  # nothing to save or dump
        sess.close()


# ---------------------------------------------------------------------------
# the chaos contract (ISSUE 9 acceptance, subprocess driver pattern)
# ---------------------------------------------------------------------------

def test_train_chaos_guard():
    """tools/check_train_faults.py end to end: SIGKILL mid-step with
    bit-identical resumed losses, crash mid-checkpoint-write with
    fallback to the previous complete checkpoint, injected NaN with
    auto-rollback + skip within bounded retries, and a SIGTERM
    preemption leaving a post-mortem + final checkpoint — each phase
    leaving its expected flight artifact."""
    import json
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.setdefault("XLA_FLAGS",
                   "--xla_force_host_platform_device_count=8")
    env.pop("PARALLAX_CKPT_FAULT", None)
    proc = subprocess.run(
        [sys.executable,
         os.path.join("tools", "check_train_faults.py")],
        env=env, capture_output=True, text=True, timeout=560,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert proc.returncode == 0, (proc.stdout[-3000:]
                                  + proc.stderr[-2000:])
    result = json.loads(proc.stdout)
    assert result["ok"], result["violations"]
    assert result["sigkill"]["loss_mismatches"] == []
    assert result["torn"]["loss_mismatches"] == []
    assert result["nan"]["completed"] and result["nan"]["surrendered"]
    assert result["preemption"]["final_checkpoint_steps"]
