"""ParallaxSession — the user-facing run loop object.

The reference monkey-patches ``tf.Session.run`` so the user's single-GPU
feeds/fetches are remapped onto the transformed graph
(reference: common/session_context.py:35-92, :179-233). Here there is no
graph to remap: ``run(fetches, feed_dict)`` executes one step of the
compiled SPMD train step and returns the requested named outputs.

Feed contract parity (session_context.py:205-233): each feed value may be
  * a single array covering this host's whole local batch, or
  * a list of ``num_replicas_per_worker`` per-replica arrays (the reference
    contract) — concatenated on dim 0 before sharding.

Fetch contract: names among {"loss", "global_step"} ∪ the model's metric
names; a single name returns a scalar, a list returns a list.

Async step pipeline (ISSUE 1): the reference hides communication behind
compute on the device; this layer hides the HOST behind the device too.
``run()`` returns lazy ``Fetch`` handles instead of eagerly pulling every
output to host, so dispatch never stalls on the previous step;
``run_async()`` makes the handle explicit; ``run_iter()`` drives a whole
batch iterator with feed conversion + host→device placement for batch
t+1 running on a background thread (bounded depth,
``ParallaxConfig.prefetch_depth``) while step t executes. Profiling
steps and the partition search keep the old blocking semantics so their
wall-times cover real device work; ``ParallaxConfig.eager_fetch=True``
restores them everywhere. ``pipeline_stats`` (profiler.PipelineStats)
records dispatch-gap / H2D-bytes / blocked-on-device per step so the
overlap is measurable (``benchmark/``) rather than assumed.

The session also owns the per-step hooks the reference installs in the
patched run: checkpoint triggers (chief-only hooks, lib.py:38-56), profile
steps (session_context.py:74-92), step timing for the partition search
(session_context.py:54-71), and — new here — the in-process partition
re-planning (the reference restarts the whole cluster per candidate;
we re-jit and reshard in place).
"""

from __future__ import annotations

import functools
import operator
import threading
import time
from typing import Any, Dict, Iterable, List, Optional, Sequence, Union

import jax
import numpy as np

from parallax_tpu.common import consts
from parallax_tpu.common.config import ParallaxConfig
from parallax_tpu.common.lib import configure_logging, parallax_log
from parallax_tpu.compile import bucketing as bucketing_lib, \
    cache as compile_cache
from parallax_tpu.core import engine as engine_lib, mesh as mesh_lib
from parallax_tpu.ckpt import CheckpointHook, RecoveryPolicy, \
    RecoverySurrender
from parallax_tpu.obs import aggregate as aggregate_lib, \
    memwatch as memwatch_lib, numwatch as numwatch_lib, trace, xprof
from parallax_tpu.obs._state import is_enabled as obs_enabled
from parallax_tpu.obs.alerts import AlertEngine, builtin_rules
from parallax_tpu.obs.anomaly import AnomalyMonitor
from parallax_tpu.obs.flightrec import FlightRecorder
from parallax_tpu.obs.goodput import GoodputLedger
from parallax_tpu.obs.journal import EventJournal
from parallax_tpu.obs.health import HealthMonitor, device_memory_stats
from parallax_tpu.obs.metrics import (JsonlSink, MetricsRegistry,
                                      PipelineStats)
from parallax_tpu.obs.timeline import StepTimeline
from parallax_tpu.profiler import ProfileHook
from parallax_tpu.parallel.partitions import PartitionSearch
from parallax_tpu.tune import calibrate as calibrate_lib, \
    costmodel as tune_costmodel
from parallax_tpu.tune.costmodel import Plan
from parallax_tpu.tune.search import MeshSearch


class Fetch:
    """Lazy handle to one fetched value.

    ``run()`` returns these (unless profiling / partition search /
    ``eager_fetch`` force blocking): the value stays on device until the
    first read, so the host thread is free to prepare batch *t+1*
    instead of stalling on step *t*'s transfer. Any read —
    ``result()``, ``float()``, ``int()``, ``np.asarray()``, arithmetic,
    comparison, formatting — materializes the host value once and
    caches it; ``shape`` / ``dtype`` / ``ndim`` / ``done()`` never
    block. Matches ``run()``'s old return values exactly on first read
    (scalars for 0-d outputs, ndarrays otherwise).
    """

    __slots__ = ("_raw", "_host", "_done", "_on_block", "_shape",
                 "_dtype")

    def __init__(self, value, on_block=None):
        self._raw = value
        self._host = None
        self._done = False
        self._on_block = on_block
        # metadata frozen at creation so shape/dtype stay stable across
        # materialization (a 0-d result becomes a Python scalar, whose
        # numpy dtype would otherwise read back widened)
        self._shape = tuple(np.shape(value))
        self._dtype = getattr(value, "dtype", None)

    def result(self):
        """Materialize (blocking until the device value is ready) and
        return the host value; cached after the first call."""
        if not self._done:
            t0 = time.perf_counter()
            with trace.span("fetch.block"):
                host = _to_host(self._raw)
            if self._on_block is not None:
                self._on_block(time.perf_counter() - t0)
            self._host = host
            self._done = True
            self._raw = None
            self._on_block = None
        return self._host

    def done(self) -> bool:
        """Non-blocking: True when the value is ready on device (or
        already materialized)."""
        if self._done:
            return True
        is_ready = getattr(self._raw, "is_ready", None)
        return bool(is_ready()) if callable(is_ready) else True

    @property
    def shape(self):
        return self._shape

    @property
    def dtype(self):
        return self._dtype

    @property
    def ndim(self) -> int:
        return len(self.shape)

    def item(self):
        return np.asarray(self.result()).item()

    def __array__(self, dtype=None, copy=None):
        return np.asarray(self.result(), dtype=dtype)

    def __float__(self):
        return float(self.result())

    def __int__(self):
        return int(self.result())

    def __index__(self):
        return operator.index(self.result())

    def __bool__(self):
        return bool(self.result())

    def __format__(self, spec):
        return format(self.result(), spec)

    def __repr__(self):
        if self._done:
            return f"Fetch({self._host!r})"
        return "Fetch(<pending>)"

    # value semantics on read: comparisons/arithmetic materialize, so
    # existing driver code (`loss < best`, `0.5 * loss`) works unchanged
    __hash__ = None

    def _binop(op, swap=False):  # noqa: N805 — descriptor factory
        def fn(self, other):
            if isinstance(other, Fetch):
                other = other.result()
            a = self.result()
            return op(other, a) if swap else op(a, other)
        fn.__name__ = ("__r" if swap else "__") + op.__name__ + "__"
        return fn

    __lt__ = _binop(operator.lt)
    __le__ = _binop(operator.le)
    __gt__ = _binop(operator.gt)
    __ge__ = _binop(operator.ge)
    __eq__ = _binop(operator.eq)
    __ne__ = _binop(operator.ne)
    __add__ = _binop(operator.add)
    __radd__ = _binop(operator.add, swap=True)
    __sub__ = _binop(operator.sub)
    __rsub__ = _binop(operator.sub, swap=True)
    __mul__ = _binop(operator.mul)
    __rmul__ = _binop(operator.mul, swap=True)
    __truediv__ = _binop(operator.truediv)
    __rtruediv__ = _binop(operator.truediv, swap=True)
    __floordiv__ = _binop(operator.floordiv)
    __rfloordiv__ = _binop(operator.floordiv, swap=True)
    __mod__ = _binop(operator.mod)
    __rmod__ = _binop(operator.mod, swap=True)
    __pow__ = _binop(operator.pow)
    __rpow__ = _binop(operator.pow, swap=True)
    del _binop

    def __neg__(self):
        return -self.result()

    def __pos__(self):
        return +self.result()

    def __abs__(self):
        return abs(self.result())


def materialize(value):
    """Resolve every ``Fetch`` inside a run() result (scalar / list /
    dict) to its host value; non-Fetch values pass through."""
    if isinstance(value, Fetch):
        return value.result()
    if isinstance(value, dict):
        return {k: materialize(v) for k, v in value.items()}
    if isinstance(value, (list, tuple)):
        items = [materialize(v) for v in value]
        if hasattr(value, "_fields"):  # namedtuple: one arg per field
            return type(value)(*items)
        return type(value)(items)
    return value


class StepHandle:
    """Returned by ``run_async()``: the step is already dispatched;
    ``result()`` blocks until every fetched value is on host and
    returns exactly what a blocking ``run()`` would have."""

    __slots__ = ("_value",)

    def __init__(self, value):
        self._value = value

    def done(self) -> bool:
        """Non-blocking readiness of every fetch in the result."""
        def ready(v):
            if isinstance(v, Fetch):
                return v.done()
            if isinstance(v, dict):
                return all(ready(x) for x in v.values())
            if isinstance(v, (list, tuple)):
                return all(ready(x) for x in v)
            return True
        return ready(self._value)

    def result(self):
        return materialize(self._value)


def _startup_entry(method):
    """A public entry point that runs before a loop: the wall seconds
    inside the OUTERMOST such call on a thread (``warmup`` calling
    ``prepare`` counts once) are added to the float counter
    ``startup.api_s``, the program's share of a caller's set-up.
    ``runner.parallel_run`` adds its own seconds to the same counter."""
    @functools.wraps(method)
    def entry(self, *args, **kwargs):
        phase = self._phase
        if getattr(phase, "in_entry", False):
            return method(self, *args, **kwargs)
        phase.in_entry = True
        t0 = time.perf_counter()
        try:
            return method(self, *args, **kwargs)
        finally:
            phase.in_entry = False
            self._api_s.inc(time.perf_counter() - t0)
    return entry


class ParallaxSession:
    def __init__(self, model: engine_lib.Model, config: ParallaxConfig,
                 num_workers: int, worker_id: int,
                 num_replicas_per_worker: int,
                 num_partitions: Optional[int] = None,
                 partition_search: Optional[PartitionSearch] = None,
                 seed: int = 0):
        self._model = model
        self._config = config
        self.num_workers = num_workers
        self.worker_id = worker_id
        self.num_replicas_per_worker = num_replicas_per_worker
        self._seed = seed
        self._num_partitions = num_partitions
        self._engine: Optional[engine_lib.Engine] = None
        self._state = None
        self._build_lock = threading.Lock()
        self._search = partition_search
        # -- auto-tuner v2 (tune/, ISSUE 10) ---------------------------
        # the full configuration the live engine was built for; every
        # engine-cache key derives from it, so plans with equal device
        # counts but different mesh shape / run option can never
        # collide into one cached engine
        self._plan: Optional[Plan] = None
        self._tune_result: Optional[Dict[str, Any]] = None
        tc = config.tune_config
        if partition_search is None and tc is not None and tc.enabled:
            # plan through MeshSearch: the cost model prices the whole
            # (dp x tp) x run_option space off the base engine's
            # lowered artifacts and only top_k plans pay measured
            # trials. PartitionSearch stays the tune_config=None path.
            self._search = MeshSearch(jax.device_count(), tc,
                                      self._default_plan())
        self._step_times: List[float] = []
        self._profile = ProfileHook(config.profile_config, worker_id)
        # -- plan observatory (obs/xprof, ISSUE 13) --------------------
        # every capture the hook stops — config-driven or on-demand
        # (profile_steps) — lands here as a pending trace, parsed
        # LAZILY at the first profile_summary()/gauge read (a
        # multi-MB JSON parse must not ride the dispatch thread)
        self._profile.set_on_stop(self._on_profile_stop)
        self._profile_pending: Optional[tuple] = None
        self._profile_attrib: Optional[Dict[str, Any]] = None
        self._last_outputs: Dict[str, Any] = {}
        # the running maxima, on the device, of the step outputs the
        # model declares as "max" gauges (Model.gauges): a poll reads them
        self._gauge_max: Dict[str, Any] = {}
        # Host-side mirror of state.step: reading the device value every
        # run() would block on the previous step and kill async dispatch.
        self._host_step = 0
        # Data-pipeline cursor: batches CONSUMED, checkpointed in the
        # manifest extras and deliberately separate from _host_step —
        # a NaN rollback rewinds the step counter but keeps consuming
        # forward (the offending batch is skipped, not replayed), so
        # only this counter tells a resumed run where its input stream
        # stands (run_iter(skip=...) / data.prefetch.skip_items).
        self._data_cursor = 0
        # -- observability (obs/): one registry for the whole runtime --
        configure_logging(config.log_level, config.log_json)
        # grow-only: the collector is process-global, and a later
        # default-config session must not truncate the ring an earlier
        # session sized up for a long capture
        if config.trace_buffer_events > trace.get_collector().capacity:
            trace.get_collector().set_capacity(config.trace_buffer_events)
        self.metrics = MetricsRegistry()
        self._api_s = self.metrics.counter("startup.api_s")
        # async pipeline stats flow through the registry (pipeline.*)
        self.pipeline_stats = PipelineStats(self.metrics)
        # -- training forensics (obs/timeline, anomaly, flightrec) -----
        # per-step wall-time attribution ring (also the flight
        # recorder's step log)
        self.timeline = StepTimeline(self.metrics,
                                     capacity=config.flight_steps)
        # thread-local step-phase scratch: data-wait/convert seconds
        # measured before _run_step on the SAME thread that dispatches
        self._phase = threading.local()
        self.anomaly = AnomalyMonitor(self.metrics,
                                      config.anomaly_config,
                                      on_event=self._on_anomaly)
        self._last_host_report: Optional[Dict] = None
        self._flops_resolved = False
        # -- ops observatory (obs/journal, goodput, alerts, ISSUE 20) --
        # Structural killswitch (the numerics pattern): with
        # PARALLAX_OBS=0 none of the three are constructed — no event
        # ring, no ledger gauges/accounting, no alert rules or state
        # (check_obs_overhead asserts the absence structurally).
        import os as _os_mod
        _run_epoch = _os_mod.environ.get("PARALLAX_RUN_EPOCH")
        self.journal = (EventJournal(
            capacity=config.journal_capacity,
            path=config.journal_path,
            max_bytes=config.journal_max_bytes,
            registry=self.metrics)
            if obs_enabled() else None)
        self.ledger = (GoodputLedger(
            self.metrics, journal=self.journal,
            run_epoch=(float(_run_epoch) if _run_epoch else None))
            if obs_enabled() else None)
        # -- checkpoint/recovery subsystem (ckpt/) ----------------------
        # the hook shares the session registry so ckpt.* metrics land
        # in the same snapshot as pipeline.*/engine.*
        self._ckpt = CheckpointHook(config.ckpt_config, worker_id,
                                    registry=self.metrics,
                                    journal=self.journal)
        self._recovery = (RecoveryPolicy(
            config.recovery_config, self.metrics,
            on_rollback=self._fire_rollback_hooks)
            if config.recovery_config.enabled else None)
        self._rollback_hooks: List[Any] = []
        self._sigterm_installed = False
        self._prev_sigterm = None
        self._session_closed = False
        self.flight = FlightRecorder(
            flight_dir=config.flight_dir, registry=self.metrics,
            journal=self.journal,
            providers={
                "progress": lambda: {"host_step": self._host_step},
                "steps": self.timeline.rows,
                "goodput": self._goodput_for_dump,
                "anomalies": lambda: self.anomaly.events(),
                "health": self._health_for_dump,
                "host_report": lambda: self._last_host_report,
                "metrics": self.metrics_snapshot,
                "device_memory": device_memory_stats,
                "config": self._config_summary,
                "ckpt": self._ckpt.stats,
                "recovery": (self._recovery.stats
                             if self._recovery is not None
                             else lambda: None),
                "tune": lambda: self._tune_result,
                "profile": self._profile_for_dump,
            })
        # -- HBM watch (obs/memwatch, ISSUE 13): live-HBM ring sampled
        # post-dispatch, per-device gauges the exporter serves, the
        # oom_risk incident class, and the compiled-peak account the
        # tuner's OOM preflight shares
        self.memwatch = memwatch_lib.MemWatch(
            self.metrics, flight=self.flight,
            capacity=config.flight_steps)
        self.flight.add_provider("memwatch", self.memwatch.stats)
        if self.journal is not None:
            # every incident artifact embeds its own causal history
            self.flight.add_provider(
                "journal_tail", lambda: self.journal.tail(64))
        if self.ledger is not None:
            self.flight.add_provider(
                "ops", lambda: self.ledger.account(self.timeline))
        # declarative alerting over the same registry: builtins (SLO
        # burn, instability, serve recompiles, page-pool exhaustion,
        # goodput floor) + user rules; polled from the step loop on
        # config.alert_interval_s and drained once more at close
        self.alerts = (AlertEngine(
            self.metrics,
            rules=(builtin_rules(config.goodput_floor)
                   + tuple(config.alert_rules)),
            journal=self.journal, flight=self.flight,
            interval_s=config.alert_interval_s)
            if obs_enabled() else None)
        if self.alerts is not None:
            self.flight.add_provider("alerts", self.alerts.summary)
        self._register_profile_gauges()
        self.health = (HealthMonitor(
            self.metrics, on_nonfinite=self._on_nonfinite,
            on_reading=self._on_health_reading)
            if config.monitor_health else None)
        # -- numerics observatory (obs/numwatch, ISSUE 17) -------------
        # Constructed ONLY when enabled AND obs is on: with
        # PARALLAX_OBS=0 no consumer, replay cache, or sentinel
        # machinery exists at all (check_obs_overhead asserts this
        # structurally), matching the engine's build-time output gate.
        self.numerics = (numwatch_lib.NumericsMonitor(
            self.metrics, config.numerics_interval,
            anomaly=self.anomaly)
            if config.numerics_interval > 0 and obs_enabled() else None)
        # last dispatched batch, kept one step for NaN provenance (the
        # engine does not donate batches, so the arrays stay readable)
        self._numerics_last_batch: Optional[tuple] = None
        self._drift_sentinels: Optional[List] = None
        self._drift_results: Optional[List[Dict]] = None
        if self.numerics is not None:
            self.flight.add_provider("numerics", self._numerics_for_dump)
        self._metrics_sink = (
            JsonlSink(self.metrics, config.metrics_path,
                      config.metrics_interval_s,
                      snapshot_fn=self.metrics_snapshot,
                      max_bytes=config.metrics_max_bytes)
            if config.metrics_path else None)
        self._last_dispatch_end: Optional[float] = None
        self._prefetcher = None
        # -- compile-ahead engine (compile/) ----------------------------
        # built engines keyed by (full plan, example-batch signature)
        # — see _build_engine: both auto-searches reuse the measured
        # winner instead of rebuilding (and recompiling) it
        self._engine_cache = compile_cache.EngineCache(self.metrics)
        # ALL background warmup threads ever started (a second
        # warmup() call must not orphan the first thread — close()
        # joins every one)
        self._warmup_threads: List[threading.Thread] = []
        compile_cache.ensure_persistent_cache(
            config.compilation_cache_dir)
        # jax's own compile events (compile.*): the process's sums,
        # read at snapshot time
        compile_cache.compile_events.expose(self.metrics)
        self._install_preemption_handler()

    # -- lazy build (needs the first batch to know shapes) ----------------

    def _ensure_engine(self, batch):
        # serialized: place_batch is documented safe from a background
        # thread ("builds the engine on first use"), so its first call
        # can race a foreground run()'s — without the lock both would
        # build (state could initialize on one engine's mesh while
        # self._engine ends up the other), or a thread could proceed on
        # pre-restore state. Always locking keeps the built path honest
        # too; uncontended acquisition is ~µs against a ms-scale step.
        with self._build_lock:
            if self._engine is not None:
                return
            self._build_engine(batch, self._num_partitions)
            # restore inside the lock: the losing thread must not see
            # the engine and run on pre-restore state
            restored = self._ckpt.restore(self._state)
            if restored is not None:
                self._state = restored
                self._apply_restored_extras()
            else:
                self._host_step = int(self._state.step)
                self._data_cursor = self._host_step
            if self._recovery is not None:
                # seed the last-good snapshot from the initial (or
                # restored) state so a NaN on the very first steps
                # already has a rollback target
                self._recovery.maybe_snapshot(self._host_step,
                                              self._state, force=True)

    def _apply_restored_extras(self) -> None:
        """Re-seat the full training closure from the manifest extras:
        the exact-resume contract is (TrainState) + (data cursor) +
        (detector baselines) — the state alone replays the wrong
        batches and re-arms the detectors on warmup noise."""
        self._host_step = int(self._state.step)
        extras = self._ckpt.restored_extras
        info = self._ckpt.last_restore_info or {}
        self._data_cursor = int(extras.get("data_cursor",
                                           self._host_step))
        self.anomaly.restore_snapshot(extras.get("anomaly"))
        if self.health is not None:
            self.health.restore_snapshot(extras.get("health"))
        if self.ledger is not None:
            # adopt the previous attempt's cumulative account; the
            # verify-restore wall books as restore_replay and the
            # kill-to-respawn gap as eviction_downtime
            self.ledger.restore_snapshot(
                extras.get("ops"),
                restore_s=self._ckpt.last_restore_seconds or 0.0)
        parallax_log.info(
            "restored checkpoint at step %d (data cursor %d)",
            self._host_step, self._data_cursor)
        if info.get("fallbacks") or info.get("torn_steps"):
            # a torn/corrupt newest checkpoint was skipped: loud in the
            # log (store.py) AND a post-mortem artifact for the fleet
            if self.journal is not None:
                self.journal.emit("ckpt", "torn_fallback",
                                  severity="warning", **dict(info))
            self.flight.trigger("ckpt_torn", dict(info))
        if self.journal is not None:
            self.journal.emit(
                "ckpt", "restored", severity="info",
                step=self._host_step, data_cursor=self._data_cursor,
                restore_s=round(
                    self._ckpt.last_restore_seconds or 0.0, 4))
        self.flight.trigger(
            "resume", {"step": self._host_step,
                       "data_cursor": self._data_cursor,
                       "restore": dict(info)})

    def _default_plan(self, num_partitions: Optional[int] = None
                      ) -> Plan:
        """The config's own configuration as a tune Plan: the legacy
        ``num_partitions`` knob (snapped to a divisor, like
        ``build_mesh`` always did) becomes the shard-axis width."""
        n = jax.device_count()
        tp = mesh_lib.snap_to_divisor(
            num_partitions if num_partitions else n, n)
        ps = self._config.communication_config.ps_config
        return Plan(dp=n // tp, tp=tp,
                    run_option=self._config.run_option,
                    sync=self._config.sync,
                    local_aggregation=ps.local_aggregation)

    def _engine_config(self, plan: Plan):
        """The config a ``plan``'s engine builds with — the session
        config with the plan's run options substituted (identity when
        they already match, the common case)."""
        import dataclasses as _dc
        cfg = self._config
        ps = cfg.communication_config.ps_config
        if (plan.run_option == cfg.run_option
                and plan.sync == cfg.sync
                and plan.local_aggregation == ps.local_aggregation):
            return cfg
        comm = _dc.replace(
            cfg.communication_config,
            ps_config=_dc.replace(
                ps, local_aggregation=plan.local_aggregation))
        return _dc.replace(cfg, run_option=plan.run_option,
                           sync=plan.sync, communication_config=comm)

    def _build_engine(self, example_batch, plan_or_partitions):
        # Bucket the example up front (no-op without shape_buckets):
        # _last_example_batch is whatever fed last, and a ragged tail
        # landing right before a replan must neither make the winner
        # lookup miss nor — under shape_buckets='auto' — re-resolve
        # the new engine's bucket set from its own odd size (the
        # bucketed example keeps 'auto' pinned to the first engine's
        # bucket across replans).
        example_batch = self._bucketed_example(example_batch)
        if isinstance(plan_or_partitions, Plan):
            plan = plan_or_partitions.validate_for(jax.device_count())
        else:
            plan = self._default_plan(plan_or_partitions)
        engine = self._engine_for_plan(plan, example_batch)
        self._engine = engine
        self._plan = plan
        if isinstance(self._search, MeshSearch) \
                and not self._search.started:
            # price the whole plan space off THIS engine's lowered
            # artifacts (host-side re-trace at worst, no compile, no
            # device step), then switch to the shortlist's first
            # candidate; the base engine stays cached for reuse. A
            # persisted calibration file (tune/calibrate.py) replaces
            # the nominal exchange rates with measured ones; the OOM
            # preflight screens the shortlist against the HBM budget
            # BEFORE any candidate pays a measured trial.
            cal = calibrate_lib.ratios(calibrate_lib.load(
                self._config.calibration_path))
            self._search.set_preflight(
                lambda p: self._preflight_peak(p, example_batch))
            first = self._search.begin(tune_costmodel.inputs_from_engine(
                engine, self._config.tune_config, calibration=cal))
            if first.cache_key() != plan.cache_key():
                parallax_log.info(
                    "mesh search: first trial %s (base plan %s kept "
                    "cached)", first.describe(), plan.describe())
                self._build_engine(example_batch, first)
                return
        if self._state is None:
            self._state = self._engine.init_state(self._seed)
        else:
            # Reshard the live state onto the new plan (auto-search);
            # the reference instead kills and relaunches the cluster
            # (partitions.py:74-138).
            self._state = self._reshard_state(self._state)

    def _engine_for_plan(self, plan: Plan, example_batch):
        """Get-or-build the engine for one plan (the cache key is the
        FULL plan + the bucketed example-batch signature — a cached
        engine keeps its jitted step's compiled executables, so a
        replan back onto a measured candidate costs a lookup + state
        reshard instead of a rebuild and a full recompile; the plan
        prefix is the ISSUE 10 collision fix). Shared by the normal
        build path and the tuner's OOM preflight."""
        key = plan.cache_key() + (
            bucketing_lib.batch_signature(example_batch),)
        engine = self._engine_cache.get(key)
        if engine is None:
            mesh = mesh_lib.build_mesh(shape=plan.mesh_shape())
            engine = engine_lib.Engine(self._model, mesh,
                                       self._engine_config(plan),
                                       example_batch,
                                       metrics=self.metrics)
            self._engine_cache.put(key, engine)
        return engine

    def _preflight_peak(self, plan: Plan, example_batch
                        ) -> Optional[int]:
        """The tuner's OOM-preflight probe: compiled-step peak bytes
        for ``plan`` (obs/memwatch.py). Builds the candidate's engine
        through the cache and pays its step compile — the same
        compile its measured trial would pay, just earlier (the
        executable lands in the engine's AOT table, so a passing
        plan's trial reuses it); a refused plan's engine is dropped
        with the other losers at search end. None = unknowable
        (backend without memory_analysis): the plan passes, refusal
        requires evidence."""
        engine = self._engine_for_plan(plan, example_batch)
        m = memwatch_lib.compiled_step_memory(engine)
        return int(m["peak_bytes"]) if m else None

    def _bucketed_example(self, example_batch):
        """The example batch as the engine will see it: bucketed when
        ``Config.shape_buckets`` is declared. Buckets resolve from the
        live engine when one exists (keeps 'auto' keying stable across
        replans — the first engine's bucket, not each ragged example's
        own size); resolution failures fall back to the raw batch (a
        conservative key: at most a redundant build, never a wrong
        engine)."""
        cfg = self._config
        if cfg.shape_buckets is None \
                or not isinstance(example_batch, dict):
            return example_batch
        try:
            buckets = (self._engine._buckets
                       if self._engine is not None else None)
            if buckets is None:
                lead = bucketing_lib._leading_dim(example_batch)
                buckets = bucketing_lib.resolve_buckets(
                    cfg.shape_buckets, lead if lead else 1)
            if not buckets:
                return example_batch
            return bucketing_lib.bucket_batch(
                example_batch, buckets, cfg.bucket_mask_feed)[0]
        except ValueError:
            return example_batch

    def _reshard_state(self, state):
        """Move the whole live state onto the new mesh. Params take the new
        plan's shardings; optimizer moments & co. keep their PartitionSpec
        names re-bound to the new mesh (axis names are stable across
        plans), so e.g. adam's mu/nu follow their sparse param's new
        shard count instead of staying on the old mesh."""
        from jax.sharding import NamedSharding
        new_mesh = self._engine.mesh
        new_params = jax.device_put(state.params,
                                    self._engine._param_shardings)

        def rebind(x):
            if hasattr(x, "sharding") and isinstance(x.sharding,
                                                     NamedSharding):
                # a plan change can also change the AXIS SET (pp > 1
                # adds 'pipe'): resolve_spec folds 'pipe' onto 'shard'
                # when the new mesh has no pipeline axis, so a 3-axis
                # plan's state reshards cleanly back onto a 2-axis one
                spec = mesh_lib.resolve_spec(x.sharding.spec, new_mesh)
                return jax.device_put(
                    x, NamedSharding(new_mesh, spec))
            return x

        rest = state.replace(params=new_params)
        return jax.tree.map(rebind, rest)

    # -- the patched-run equivalent ---------------------------------------

    @_startup_entry
    def prepare(self, feed_dict: Dict[str, Any]) -> int:
        """Build the engine (and restore any configured checkpoint)
        from an example batch WITHOUT running a step; returns the
        restored global step (0 on a fresh run). Lets callers read
        ``state``/``engine``/the mesh — or seed per-step data correctly
        on an elastic resume — before the first training step."""
        with trace.span("session.prepare"):
            self._ensure_engine(self._convert_feed(feed_dict))
        return int(self._state.step)

    @_startup_entry
    def run(self, fetches: Union[None, str, Sequence[str]] = None,
            feed_dict: Optional[Dict[str, Any]] = None):
        if feed_dict is None:
            raise ValueError(
                "ParallaxSession.run requires feed_dict (the batch); "
                "fetch-only runs have no meaning under SPMD")
        batch = self._convert_feed(feed_dict)
        self._ensure_engine(batch)
        return self._run_step(fetches, batch)

    def run_async(self, fetches: Union[None, str, Sequence[str]] = None,
                  feed_dict: Optional[Dict[str, Any]] = None
                  ) -> StepHandle:
        """``run()`` with the future made explicit: dispatches one step
        and returns a ``StepHandle`` immediately; ``handle.result()``
        blocks until the fetches are on host and returns exactly what a
        blocking ``run()`` would. Ignores ``eager_fetch`` (the whole
        point is not to block); profiling steps / the partition search
        still block inside the dispatch so their timings stay honest."""
        if feed_dict is None:
            raise ValueError(
                "ParallaxSession.run_async requires feed_dict (the "
                "batch); fetch-only runs have no meaning under SPMD")
        batch = self._convert_feed(feed_dict)
        self._ensure_engine(batch)
        return StepHandle(self._run_step(fetches, batch, force_lazy=True))

    def run_iter(self, batches: Iterable[Dict[str, Any]],
                 fetches: Union[None, str, Sequence[str]] = None,
                 placed: bool = False,
                 skip: Union[int, str] = 0):
        """Pipelined training loop: yields one ``run()`` result per feed
        dict from ``batches``, with feed conversion, ``feed_transforms``
        and host→device placement for batch *t+1* running on a bounded
        background thread (depth ``ParallaxConfig.prefetch_depth``)
        while step *t* executes on device. Results come back in batch
        order with the exact ``run()`` fetch contract — same losses,
        bit for bit, as the sequential loop.

        With ``Config.shape_buckets`` declared, every batch — above
        all the final partial one, the classic silent-retrace case —
        is padded onto its bucket inside ``shard_batch``, so a ragged
        iterator presents a bounded signature set and
        ``engine.recompiles`` stays 0 (pair with ``session.warmup()``
        to also pay those compiles before step 0).

        ``placed=True`` skips the internal prefetcher and treats each
        item as already device-placed (chain
        ``data.prefetch_to_device(batches, session.place_batch)`` for
        an external pipeline, e.g. straight off the native token
        loader's thread).

        ``skip`` fast-forwards that many items of ``batches`` before
        the first step — the checkpoint resume protocol: rebuild the
        SAME stream from its start and pass
        ``skip=session.data_cursor`` (or the literal ``"auto"``, which
        reads the restored cursor after ``prepare()``); the resumed
        run's batches are then bit-identical to the uninterrupted
        run's. Skipping pays only iteration cost
        (``data.prefetch.skip_items`` — no conversion, no H2D) and
        raises if the stream ends inside the skip window.

        While the partition auto-search is live the loop stays
        sequential (a replan rebuilds the mesh, which would invalidate
        in-flight placed batches) and upgrades to prefetching the step
        after the search settles. Exceptions from the iterator or the
        prefetch thread surface here, at the step that would have
        consumed the failed batch; closing the generator (or
        ``session.close()``) shuts the thread down."""
        # validate placed=True misuse HERE, not at the first next(): a
        # generator body only runs on iteration, which can be far from
        # the offending call site
        if skip == "auto":
            if self._engine is None:
                # the cursor is only known AFTER the checkpoint
                # restore; resolving it against a not-yet-built session
                # would silently skip 0 and retrain the consumed prefix
                raise ValueError(
                    "run_iter(skip='auto') before the engine exists: "
                    "the restored data cursor is only known after the "
                    "checkpoint restore — call prepare(example_feed) "
                    "first (or pass an explicit skip count)")
            skip = self._data_cursor
        if placed and self._search is not None:
            # a replan would rebuild the mesh under batches the
            # external pipeline already placed for the old one
            raise ValueError(
                "run_iter(placed=True) cannot run while an "
                "auto-search (partition or mesh) is live: a replan "
                "would invalidate already-placed batches. Finish the "
                "search first (or disable search_partitions / "
                "tune_config).")
        it = iter(batches)
        if int(skip):
            from parallax_tpu.data.prefetch import skip_items
            # synchronous, before the generator: a bad cursor raises
            # at the call site, not at the first next()
            it = skip_items(it, int(skip))
        return self._run_iter_gen(it, fetches, placed)

    def _next_timed(self, it):
        """``next(it)`` with the wait attributed as the step's
        data-wait (the input-stall lane of the timeline and the
        chrome trace); StopIteration propagates."""
        t0 = time.perf_counter()
        try:
            with trace.span("session.data_wait"):
                return next(it)
        finally:
            self._phase.data_wait_s = time.perf_counter() - t0

    def _run_iter_gen(self, it, fetches, placed):
        if placed:
            while True:
                try:
                    batch = self._next_timed(it)
                except StopIteration:
                    return
                # checked per batch, not at call time: the documented
                # prefetch_to_device chaining builds the engine lazily
                # on ITS background thread (place_batch), and the queue
                # hand-off guarantees it exists once a batch arrives —
                # only batches placed by other means can get here first
                if self._engine is None:
                    raise ValueError(
                        "run_iter(placed=True) got a batch but no "
                        "engine exists: place batches via "
                        "session.place_batch (which builds it) or "
                        "call prepare(example_feed) first")
                yield self._run_step(fetches, batch, placed=True)
        # sequential while the partition search may rebuild the mesh
        while self._search is not None:
            try:
                feed = next(it)
            except StopIteration:
                return
            batch = self._convert_feed(feed)
            self._ensure_engine(batch)
            yield self._run_step(fetches, batch)
        from parallax_tpu.data.prefetch import Prefetcher
        prefetcher = Prefetcher(it, self.place_batch,
                                depth=int(self._config.prefetch_depth),
                                name="parallax-feed-prefetch")
        self._prefetcher = prefetcher
        try:
            while True:
                try:
                    batch = self._next_timed(prefetcher)
                except StopIteration:
                    break
                yield self._run_step(fetches, batch, placed=True)
        finally:
            prefetcher.close()
            if self._prefetcher is prefetcher:
                # a stale generator's finalization must not clobber the
                # tracking of a newer run_iter's live prefetcher
                self._prefetcher = None

    def place_batch(self, feed_dict: Dict[str, Any]):
        """Convert one feed dict (per-replica lists, ``feed_transforms``)
        and place it onto the mesh — everything ``run()`` does before
        dispatch, without the step. Safe to call from a background
        thread once the engine exists; builds the engine on first use.
        Feed the result to ``run_iter(..., placed=True)`` or
        ``engine.step(state, batch, preplaced=True)``."""
        batch = self._convert_feed(feed_dict)
        self._ensure_engine(batch)
        self.pipeline_stats.record_h2d(_feed_nbytes(batch))
        return self._engine.shard_batch(batch)

    def _run_step(self, fetches, batch, placed: bool = False,
                  force_lazy: bool = False):
        """Dispatch one step on an already-converted (and possibly
        already-placed) batch; shared by run/run_async/run_iter."""
        step = self._host_step
        # pop this thread's pre-dispatch phase measurements (run_iter's
        # wait on the prefetcher, _convert_feed on this thread)
        data_wait_s = getattr(self._phase, "data_wait_s", 0.0)
        self._phase.data_wait_s = 0.0
        convert_s = getattr(self._phase, "convert_s", 0.0)
        self._phase.convert_s = 0.0
        # placement this thread already paid before the step call (the
        # place_batch-then-step pattern): part of this step's H2D, but
        # NOT inside dt — popped separately so the dispatch share isn't
        # corrupted by subtracting time it never contained
        h2d_pre_s = (self._engine.pop_h2d_seconds()
                     if self._engine is not None else 0.0)
        self._profile.before_step(step)
        t0 = time.perf_counter()
        gap = (None if self._last_dispatch_end is None
               else t0 - self._last_dispatch_end)
        blocked_s = 0.0
        try:
            with trace.span("session.dispatch", step=step):
                if not placed:
                    self.pipeline_stats.record_h2d(_feed_nbytes(batch))
                self._state, outputs = self._engine.step(
                    self._state, batch, preplaced=placed)
                # debug_nans blocks too: its contract is "raise at the
                # step that produced the NaN", which lazy fetches would
                # defer to whatever later line first reads a value
                blocking = (self._search is not None
                            or self._profile.active
                            or self._config.debug_nans
                            or (self._config.eager_fetch
                                and not force_lazy))
                if blocking:
                    # Block so step timing / traces cover real device
                    # work.
                    tb = time.perf_counter()
                    # tree_map, not a flat dict-comp: the numerics
                    # output is itself a stats tree
                    outputs = jax.tree_util.tree_map(np.asarray,
                                                     outputs)
                    blocked_s = time.perf_counter() - tb
                    self.pipeline_stats.record_blocked(blocked_s)
        except Exception as e:
            # post-mortem without rerunning: the bounded history is
            # dumped the moment a step dies (flight_dir configured);
            # the exception itself propagates untouched
            self.flight.trigger(
                f"exception:{type(e).__name__}",
                {"step": step, "error": f"{type(e).__name__}: {e}"})
            raise
        now = time.perf_counter()
        dt = now - t0
        self._last_dispatch_end = now
        self.pipeline_stats.record_dispatch(gap, dt)
        # step-time attribution (obs/timeline.py): wall = dispatch-end
        # to dispatch-end; the engine's thread-local H2D share covers
        # only a placement THIS thread just paid (preplaced batches
        # overlapped it on the prefetch thread). The first step has no
        # previous dispatch to anchor a gap, so its wall is its own
        # measured pre-phases + dispatch (otherwise a step-0 data wait
        # — the engine build — would exceed its wall and break the
        # goodput fractions).
        wall_s = (gap if gap is not None
                  else data_wait_s + convert_s) + dt
        row = self.timeline.record_step(
            step, t0, wall_s, data_wait_s=data_wait_s,
            convert_s=convert_s, h2d_s=self._engine.pop_h2d_seconds(),
            dispatch_s=dt, fetch_block_s=blocked_s,
            h2d_pre_s=h2d_pre_s)
        if self.ledger is not None:
            # run-lifetime account: this step's wall becomes
            # productive time minus its data-wait lane (obs/goodput)
            self.ledger.on_step(row)
        self.anomaly.observe("step_time_ms", step, wall_s * 1e3)
        # live-HBM sample post-dispatch (no-op on backends without
        # memory_stats, structural no-op under the obs killswitch)
        self.memwatch.sample(step)
        self._profile.after_step(step)
        self._last_outputs = outputs
        for gauge, (entry, mode) in self._model.gauges.items():
            value = outputs.get(entry)
            if mode == "max" and value is not None:
                # one such step must not hide between two polls: the
                # maximum is kept on the device, dispatched and not read
                seen = self._gauge_max.get(gauge)
                self._gauge_max[gauge] = (
                    value if seen is None
                    else jax.numpy.maximum(seen, value))
        if self.numerics is not None:
            # cache the batch BEFORE recovery looks at the outputs: if
            # this step trips, provenance sweeps exactly these feeds
            self._numerics_last_batch = (step, batch)
            self.numerics.observe(step, outputs.get("numerics"))
            di = self._config.numerics_drift_interval
            if di and step and step % di == 0:
                self._run_drift_sentinels_guarded(step)
        new_step = step + 1
        self._host_step = new_step
        self._data_cursor += 1
        if self._recovery is not None:
            # step-granular NaN detection (blocks on this step's
            # in-graph health scalars — the documented recovery trade):
            # a non-finite step rolls the state back to the last-good
            # snapshot and the offending batch is skipped
            self._maybe_recover(step, outputs)
        if self.health is not None:
            # lazy: only already-transferred values are read, so the
            # dispatch thread never blocks on monitoring. `step` (the
            # pre-increment index) matches the session.dispatch span and
            # ProfileHook numbering, so a NaN warning cross-references
            # the trace/profile of the step that produced it.
            self.health.observe(step, outputs.get("loss_finite"),
                                outputs.get("grad_norm"),
                                loss=outputs.get("loss"))
        t_ck = time.perf_counter()
        if self._ckpt.maybe_save(self._host_step, self._state,
                                 extras_fn=self._ckpt_extras):
            self._warn_sparse_overflow("checkpoint")
            if self.ledger is not None:
                # the save's host wall lands inside the next step's
                # dispatch gap too, so the ledger carves it back out
                # of productive rather than double-counting
                self.ledger.note_badput(
                    "ckpt_stall", time.perf_counter() - t_ck,
                    carve_from_productive=True)
        if self.alerts is not None:
            # cheap clock compare; a full rule pass only every
            # config.alert_interval_s
            self.alerts.poll()
        if self._search is not None:
            self._record_search_time(dt)
        return self._convert_fetch(fetches, outputs, lazy=not blocking,
                                   step=step)

    @property
    def state(self):
        return self._state

    def set_model_state(self, model_state) -> None:
        """Replace the non-trainable state of a stateful ``Model`` (the
        step's ``model_state``: statistics, a router's balancing biases)
        with a tree of the same shapes, placed as the old one was. What
        a step does not learn by gradient a caller may bring to rest
        before training starts; parameters and optimizer state stay."""
        old = self._state.model_state
        if jax.tree.structure(old) != jax.tree.structure(model_state):
            raise ValueError("model_state of another structure than the "
                             "model's own")
        new = jax.tree.map(
            lambda o, n: jax.device_put(
                np.asarray(n, o.dtype).reshape(o.shape), o.sharding),
            old, model_state)
        self._state = self._state.replace(model_state=new)

    @property
    def engine(self):
        return self._engine

    def layer_index(self) -> Optional[Dict[str, Any]]:
        """``Engine.layer_index()`` of the live engine: which layer
        (obs/xprof.LAYER_SCOPES) each instruction of the compiled step
        belongs to. None before the engine exists or without an AOT
        executable (``warmup()``); still answers after ``close()``."""
        return (self._engine.layer_index()
                if self._engine is not None else None)

    @property
    def plan(self) -> Optional[Plan]:
        """The full configuration the live engine was built for (mesh
        shape + run options), or None before the engine exists."""
        return self._plan

    def tune_summary(self) -> Optional[Dict[str, Any]]:
        """The mesh auto-tuner's decision record once the search has
        settled (candidates enumerated / pruned / trialed, per-trial
        predicted-vs-measured ms, the winner's ratio, search wall
        seconds — see ``tune.MeshSearch.summary``), else None. Also a
        flight-recorder provider."""
        return self._tune_result

    # -- plan observatory (obs/xprof + obs/memwatch, ISSUE 13) ------------

    def profile_steps(self, n: int,
                      outdir: Optional[str] = None) -> Optional[str]:
        """Arm a windowed ``jax.profiler`` capture of the NEXT ``n``
        steps; returns the capture directory (or None on a worker the
        ``ProfileConfig.profile_worker`` gating excludes — one trace
        per pod, like the config-driven windows). The captured steps
        run BLOCKING (``ProfileHook.active`` forces it) so the trace
        covers real device work; once the window closes, the trace is
        parsed lazily at the first :meth:`profile_summary` call into
        the per-op / per-collective attribution (obs/xprof.py),
        exported as the lazy ``profile.*`` gauges and a chrome-lane
        summary. ``outdir`` defaults under ``profile_dir`` when
        configured, else a fresh temp directory."""
        import os as _os
        import tempfile
        # gate/validate BEFORE allocating a directory: an excluded
        # worker (or a second call mid-capture) must not leak one
        # abandoned temp dir per call
        if not self._profile.worker_enabled:
            return None
        if self._profile.capture_busy:
            raise RuntimeError(
                "a profile capture is already armed/in flight; wait "
                "for it to finish before requesting another window")
        if int(n) < 1:
            raise ValueError(
                f"profile window must cover >= 1 step, got {n}")
        if outdir is None:
            base = self._config.profile_config.profile_dir
            if base:
                outdir = _os.path.join(
                    base, f"window_step{self._host_step}")
            else:
                outdir = tempfile.mkdtemp(prefix="parallax-xprof-")
        ok = self._profile.request_window(self._host_step, n, outdir)
        return outdir if ok else None

    def _on_profile_stop(self, trace_dir: str, steps: int) -> None:
        """ProfileHook callback (dispatch thread): record the pending
        capture; the multi-MB JSON parse happens at the first
        profile_summary() read, never on the step path."""
        self._profile_pending = (trace_dir, int(steps))
        parallax_log.info(
            "profile window complete: %d step(s) captured in %s "
            "(profile_summary() parses it)", steps, trace_dir)

    def profile_summary(self) -> Optional[Dict[str, Any]]:
        """The latest capture window's measured attribution (the
        obs/xprof ``Attribution.as_dict()``: category shares,
        per-collective totals, top ops with layer / dense-sparse
        mapping, and the explicit residual + coverage), parsing any
        pending trace first. None before any window completed; a
        failed parse returns ``{"error": ...}`` rather than
        masquerading as data."""
        pending, self._profile_pending = self._profile_pending, None
        if pending is None:
            return self._profile_attrib
        path, steps = pending
        try:
            trace_doc, tpath = xprof.load_trace(path)
            layer_idx = self.layer_index()
            attrib = xprof.attribute(
                trace_doc, steps=steps, source=tpath,
                hlo_index=layer_idx and layer_idx["hlo_index"])
            self._profile_attrib = attrib.as_dict()
            self._emit_profile_lanes(attrib)
            parallax_log.info(
                "profile attribution: %.1f%% of %.2fms device wall "
                "attributed (residual %.2fms) over %d op event(s)",
                100.0 * (attrib.coverage or 0.0), attrib.wall_ms,
                attrib.residual_ms, attrib.events)
        except Exception as e:
            parallax_log.warning("profile attribution failed: %s", e)
            self._profile_attrib = {
                "error": f"{type(e).__name__}: {e}", "source": path}
        return self._profile_attrib

    def _emit_profile_lanes(self, attrib) -> None:
        """Chrome-lane summary of the parsed window: one span per
        category (duration = its self-time) plus the residual lane,
        so the obs chrome export shows the measured split next to
        the host-side spans."""
        t0 = time.perf_counter()
        for cat, row in attrib.by_category.items():
            trace.record_span("profile." + cat, t0,
                              t0 + row["self_ms"] / 1e3,
                              share=row["share"],
                              events=row["events"])
        trace.record_span("profile.residual", t0,
                          t0 + attrib.residual_ms / 1e3,
                          coverage=attrib.coverage)

    def _register_profile_gauges(self) -> None:
        """Lazy ``profile.*`` gauges over the latest PARSED
        attribution — sampled at snapshot time, zero per-step cost,
        and they never trigger a parse themselves (a metrics scrape
        must stay cheap)."""
        def top(key):
            a = self._profile_attrib
            return a.get(key) if isinstance(a, dict) else None

        def share(cat):
            a = self._profile_attrib
            if not isinstance(a, dict):
                return None
            row = (a.get("by_category") or {}).get(cat)
            return row.get("share") if row else None

        g = self.metrics.gauge
        g("profile.attribution_coverage").set_fn(
            lambda: top("coverage"))
        g("profile.residual_ms").set_fn(lambda: top("residual_ms"))
        g("profile.step_wall_ms").set_fn(
            lambda: top("step_wall_ms"))
        g("profile.steps").set_fn(lambda: top("steps"))
        for cat in xprof.CATEGORIES:
            g(f"profile.share.{cat}").set_fn(
                lambda c=cat: share(c))

    def _profile_for_dump(self) -> Optional[Dict[str, Any]]:
        """Flight-recorder section: the parsed attribution when one
        exists; a pending-capture pointer otherwise (an incident dump
        must not pay a trace parse mid-incident)."""
        if self._profile_attrib is not None:
            return self._profile_attrib
        if self._profile_pending is not None:
            return {"pending_trace": self._profile_pending[0],
                    "steps": self._profile_pending[1],
                    "note": "unparsed; profile_summary() parses it"}
        return None

    def write_calibration(self, path: Optional[str] = None) -> str:
        """Close the cost-model loop: compare the settled mesh
        search's per-term predictions for the WINNER plan against the
        measured per-op aggregates of the latest profile window, and
        persist the per-term ``predicted_over_measured`` ratios
        (tune/calibrate.py) to ``path`` (default
        ``Config.calibration_path``). The next search on this rig
        loads them in place of nominal constants. Requires both a
        settled tune decision and a parsed profile window — refuses
        loudly otherwise."""
        path = path or self._config.calibration_path
        if not path:
            raise ValueError(
                "write_calibration needs a path: pass one or set "
                "Config.calibration_path")
        attrib = self.profile_summary()
        if not attrib or attrib.get("error") \
                or not attrib.get("by_category"):
            raise ValueError(
                "write_calibration needs a parsed profile window: "
                "arm session.profile_steps(n), run those steps, then "
                "retry (last attribution: %r)"
                % (attrib.get("error") if attrib else None))
        tune = self._tune_result
        if not tune or not tune.get("winner"):
            raise ValueError(
                "write_calibration needs a settled mesh search "
                "(Config.tune_config): the calibration compares the "
                "winner's predicted terms against the measured ones")
        entry = next((e for e in tune.get("scored", [])
                      if e.get("plan") == tune["winner"]["plan"]),
                     None)
        if entry is None or not entry.get("terms_ms"):
            raise ValueError(
                "tune decision record carries no per-term breakdown "
                "for the winner; cannot calibrate")
        terms_s = {k: float(v) / 1e3
                   for k, v in entry["terms_ms"].items()}
        predicted = calibrate_lib.predicted_terms_from_cost(terms_s)
        # the scored terms are CALIBRATED when this search loaded a
        # calibration file — un-apply the stored ratios so the new
        # record compares the NOMINAL prediction against the measured
        # world (otherwise recalibrating off a calibrated run yields
        # ratios ~1 and the next generation swings back to nominal,
        # oscillating forever). Exact under sync=True; under
        # sync=False the hidden-wire overlap makes it approximate.
        applied = entry.get("calibration") or {}
        for term in calibrate_lib.TERMS:
            r = applied.get(term)
            if r:
                predicted[term] *= float(r)
        measured = calibrate_lib.measured_terms_from_attribution(
            attrib, jax.device_count())
        if measured is None:
            raise ValueError(
                "profile window carried no usable device ops; "
                "cannot calibrate")
        record = calibrate_lib.build_record(
            predicted, measured, basis=tune.get("cost_basis",
                                                "nominal"),
            meta={"plan": tune["winner"]["plan"],
                  "platform": jax.devices()[0].platform,
                  "num_devices": jax.device_count(),
                  "steps_profiled": attrib.get("steps"),
                  "coverage": attrib.get("coverage")})
        return calibrate_lib.save(path, record)

    def sparse_overflow_steps(self) -> int:
        """Total row_sparse_adagrad overflow events so far: steps that
        touched more rows than max_touched_rows and silently DROPPED
        their lowest-activity rows. Nonzero => raise the bound.
        (ops/sparse_optim.collect_overflow_steps on the live state.)"""
        if self._state is None:
            return 0
        from parallax_tpu.ops.sparse_optim import collect_overflow_steps
        return collect_overflow_steps(self._state.opt_state)

    @property
    def steps_per_sec(self) -> Optional[float]:
        """Rolling dispatch throughput over the last <=20 steps (the
        framework-side metric the reference left to user drivers);
        lives in the registry as the ``pipeline.steps_per_sec`` gauge."""
        return self.pipeline_stats.steps_per_sec()

    def metrics_snapshot(self) -> Dict:
        """One JSON-ready dict of every runtime metric — pipeline
        overlap (dispatch gap / H2D bytes / blocked-on-device /
        steps-per-sec), engine builds + recompiles, health counters when
        enabled — with the polled gauges (sparse overflow, device
        memory) refreshed first. Safe to call from a monitoring thread
        while training is live (``benchmark/`` reads it after a window)."""
        try:
            self.metrics.gauge("sparse.overflow_steps").set(
                self.sparse_overflow_steps())
        except Exception:
            # reading live opt_state can race step donation; the stale
            # gauge value is better than killing a monitoring thread
            pass
        # the step outputs the model declares as gauges (Model.gauges),
        # as of the last dispatched step: reading them waits for that
        # step here, never in the step loop
        for gauge, (entry, mode) in self._model.gauges.items():
            value = (self._gauge_max.get(gauge) if mode == "max"
                     else self._last_outputs.get(entry))
            if value is not None:
                try:
                    self.metrics.gauge(gauge).set(float(value))
                except Exception:
                    pass    # a donated buffer: keep the stale value
        for dev, stats in device_memory_stats().items():
            for key in ("bytes_in_use", "peak_bytes_in_use"):
                if key in stats:
                    self.metrics.gauge(f"memory.{dev}.{key}").set(
                        stats[key])
        if self.health is not None:
            try:
                self.health.poll()
            except Exception:
                # same class of live-state race as the overflow gauge
                # above: a poisoned buffer must not kill the caller
                pass
        if self.numerics is not None:
            try:
                self.numerics.poll()
            except Exception:
                pass
        return self.metrics.snapshot()

    # -- training forensics (obs/) ----------------------------------------

    def _on_anomaly(self, event) -> None:
        """AnomalyMonitor callback: log + flight-dump the incident."""
        parallax_log.warning(
            "anomaly: %s %s at step %d — value %.4g vs baseline %.4g "
            "(%.2fx)", event.signal, event.kind, event.step, event.value,
            event.baseline, event.ratio)
        if self.health is not None:
            # anomaly events feed the instability score (ROADMAP item
            # 4's cadence hook): numerics trends (update-ratio /
            # underflow per layer) weigh more than a step-time blip —
            # they are the signals that precede a blow-up. Non-finite
            # incidents add weight 1.0 inside HealthMonitor itself.
            self.health.record_instability_event(
                0.5 if event.signal.startswith(("numerics.", "loss",
                                                "grad_norm")) else 0.25)
        if self.journal is not None:
            # journaled BEFORE the flight trigger so the dump's own
            # journal_tail section already shows this event
            self.journal.emit(
                "anomaly", event.kind, severity="warning",
                signal=event.signal, step=event.step,
                value=event.value, baseline=event.baseline,
                ratio=event.ratio)
        self.flight.trigger(
            f"anomaly_{event.signal}_{event.kind}",
            {"signal": event.signal, "kind": event.kind,
             "step": event.step, "value": event.value,
             "baseline": event.baseline, "ratio": event.ratio})

    def _on_nonfinite(self, step: int, kind: str) -> None:
        """HealthMonitor callback: a NaN/Inf loss or grad norm is a
        flight-dump incident the moment it is consumed."""
        self.flight.trigger(f"nonfinite_{kind}", {"step": step})

    def _on_health_reading(self, step: int, loss, grad_norm) -> None:
        """Finite per-step health values feed the spike detectors."""
        if loss is not None and np.isfinite(loss):
            self.anomaly.observe("loss", step, float(loss))
        if grad_norm is not None and np.isfinite(grad_norm):
            self.anomaly.observe("grad_norm", step, float(grad_norm))

    # -- numerics observatory (obs/numwatch, ISSUE 17) --------------------

    def _numerics_provenance(self, step: int, kind: str,
                             outputs) -> Dict:
        """Blast-radius sweep for the nonfinite_rollback artifact: the
        cached offending batch, the (pre-rollback) param tree, the trip
        step's forced in-graph grad stats, and the loss, in dataflow
        order. Blocking — the rollback is already stalling dispatch."""
        batch = None
        if (self._numerics_last_batch is not None
                and self._numerics_last_batch[0] == step):
            batch = self._numerics_last_batch[1]
        return numwatch_lib.provenance_report(
            feeds=batch,
            params=(self._state.params
                    if self._state is not None else None),
            trip_stats=outputs.get("numerics"),
            loss=outputs.get("loss"),
            step=step, kind=kind)

    def run_drift_sentinels(self) -> Optional[List[Dict]]:
        """Shadow-eval every hand-built kernel executor against its
        reference NOW (LSTM bwd kernel vs scan, paged-attn kernel vs
        einsum) and return the check results; gauges land as
        ``numerics.drift.<name>.*``. Runs whole milliseconds of kernel
        work — the in-loop cadence is ``numerics_drift_interval`` (off
        by default); this method is the explicit entry point.
        None when the numerics observatory is off."""
        if self.numerics is None:
            return None
        if self._drift_sentinels is None:
            self._drift_sentinels = numwatch_lib.default_sentinels(
                self.metrics)
        results = [s.check() for s in self._drift_sentinels]
        self._drift_results = results
        for r in results:
            if r["flagged"]:
                parallax_log.warning(
                    "numerics: drift sentinel %r flagged — rel_err "
                    "%.3e (tol %.1e), argmax flips %s", r["name"],
                    r["rel_err"], r["rel_err_tol"],
                    r["argmax_flip_frac"])
                if self.journal is not None:
                    self.journal.emit(
                        "numerics", "kernel_drift",
                        severity="warning", name=r["name"],
                        rel_err=r["rel_err"],
                        argmax_flip_frac=r["argmax_flip_frac"])
                self.flight.trigger(
                    f"kernel_drift_{r['name']}", dict(r))
        return results

    def _run_drift_sentinels_guarded(self, step: int) -> None:
        try:
            with trace.span("numerics.drift_sweep", step=step):
                self.run_drift_sentinels()
        except Exception as e:
            # a broken shadow-eval must never fail the training step
            parallax_log.warning("drift sentinel sweep failed: %s", e)

    def _numerics_for_dump(self) -> Optional[Dict]:
        """Non-blocking numerics flight section (trail + drift)."""
        if self.numerics is None:
            return None
        out = self.numerics.snapshot_for_dump()
        out["drift"] = self._drift_results
        return out

    # -- checkpoint/recovery (ckpt/) --------------------------------------

    @property
    def data_cursor(self) -> int:
        """Batches consumed so far (including any a NaN rollback
        skipped) — the input-stream position the checkpoint commits.
        After a restore, skip this many items of the rebuilt stream
        (``run_iter(..., skip=sess.data_cursor)`` or
        ``data.prefetch.skip_items``) for bit-identical resumption."""
        return self._data_cursor

    def _ckpt_extras(self) -> Dict[str, Any]:
        """The exact-resume closure beyond the TrainState, committed
        inside the checkpoint manifest."""
        return {
            "data_cursor": self._data_cursor,
            "host_step": self._host_step,
            "anomaly": self.anomaly.snapshot(),
            "health": (self.health.snapshot()
                       if self.health is not None else None),
            "recovery": (self._recovery.stats()
                         if self._recovery is not None else None),
            # cumulative goodput/badput totals: a resumed run reports
            # the account ACROSS attempts (obs/goodput.py)
            "ops": (self.ledger.snapshot()
                    if self.ledger is not None else None),
        }

    def set_rollback_hook(self, fn) -> None:
        """Register ``fn(consecutive_retries)`` to run on every NaN
        rollback — the LR-backoff seam: pair with
        ``optax.inject_hyperparams`` and shrink the learning rate per
        retry so the retried region re-enters a stable regime."""
        self._rollback_hooks.append(fn)

    def _fire_rollback_hooks(self, retries: int) -> None:
        for fn in self._rollback_hooks:
            try:
                fn(retries)
            except Exception as e:
                parallax_log.warning("rollback hook failed: %s", e)

    def _maybe_recover(self, step: int, outputs) -> bool:
        """Inspect this step's in-graph health scalars; on a non-finite
        loss/grad roll back to the last-good snapshot (batch skipped —
        the data cursor keeps advancing). Raises RecoverySurrender
        after ``max_retries`` consecutive failures. Returns True when a
        rollback happened."""
        lf = outputs.get("loss_finite")
        gn = outputs.get("grad_norm")
        kind = None
        if lf is not None and not bool(np.asarray(lf)):
            kind = "loss"
        elif gn is not None and not np.isfinite(float(np.asarray(gn))):
            kind = "grad"
        if kind is None:
            # a finite step: refresh the last-good snapshot on cadence
            # and reset the consecutive-failure budget
            self._recovery.note_good_step()
            self._recovery.maybe_snapshot(self._host_step, self._state)
            return False
        detail = {"step": step, "kind": kind,
                  "snapshot_step": self._recovery.snapshot_step,
                  "data_cursor": self._data_cursor}
        if self.numerics is not None:
            # NaN provenance (obs/numwatch.py): this runs BEFORE the
            # rollback below, so self._state is still the poisoned
            # post-step tree and the cached batch is the offending one
            # — the artifact names the first non-finite stage and
            # carries the stats trail leading in. Guarded: forensics
            # must never break the recovery they decorate.
            try:
                detail["provenance"] = self._numerics_provenance(
                    step, kind, outputs)
                self.numerics.poll(block=True)
                detail["stats_trail"] = self.numerics.trail_tail(16)
            except Exception as e:
                detail["provenance_error"] = f"{type(e).__name__}: {e}"
        if self.journal is not None:
            self.journal.emit(
                "recovery", "nonfinite_rollback", severity="error",
                step=step, kind=kind,
                snapshot_step=self._recovery.snapshot_step,
                data_cursor=self._data_cursor)
        self.flight.trigger("nonfinite_rollback", detail)
        try:
            state, snap_step = self._recovery.rollback(step, kind)
        except RecoverySurrender as e:
            if self.journal is not None:
                self.journal.emit(
                    "recovery", "surrender", severity="error",
                    step=step, kind=kind,
                    rollbacks=self._recovery.total_rollbacks)
            self.flight.trigger(
                "recovery_surrender",
                {"step": step, "kind": kind, "error": str(e),
                 "rollbacks": self._recovery.total_rollbacks})
            raise
        self._state = state
        self._host_step = snap_step
        if self.ledger is not None:
            # the rewound steps trained nothing: their measured step
            # time moves into the rollback_discarded badput class
            discarded_s = self.ledger.on_rollback(snap_step)
            if self.journal is not None:
                self.journal.emit(
                    "ops", "rollback_discarded", severity="warning",
                    to_step=snap_step,
                    discarded_s=round(discarded_s, 4))
        return True

    def on_preemption(self, signum: Optional[int] = None) -> None:
        """The eviction path (SIGTERM by default): leave a
        ``preemption`` post-mortem and attempt ONE final synchronous
        checkpoint of the current state. Best-effort end to end — an
        evicted worker must never die harder because its last-gasp
        forensics failed."""
        if self._session_closed:
            # a closed session's handler can survive inside a newer
            # session's chain; it must pass the signal through without
            # dumping/saving stale state
            return
        try:
            if self.journal is not None:
                self.journal.emit(
                    "preempt", "sigterm", severity="warning",
                    signal=signum, step=self._host_step,
                    data_cursor=self._data_cursor)
            self.flight.trigger(
                "preemption",
                {"signal": signum, "step": self._host_step,
                 "data_cursor": self._data_cursor})
        except Exception:
            pass
        if self._ckpt.enabled and self._state is not None:
            self._ckpt.save_now(self._host_step, self._state,
                                extras=self._ckpt_extras(),
                                reason="preemption")

    def _install_preemption_handler(self) -> None:
        """SIGTERM -> on_preemption, then the previous disposition.
        Installed only when something would be saved (flight_dir or
        ckpt_dir) and only from the main thread (the signal module's
        own restriction)."""
        import signal
        if not self._config.handle_preemption:
            return
        if not (self._config.flight_dir or self._ckpt.enabled):
            return
        if threading.current_thread() is not threading.main_thread():
            return
        try:
            # keep the EXACT installed object: bound-method access
            # creates a fresh object each time, and uninstall must be
            # able to ask "is the live handler still mine?"
            self._sigterm_handler = self._handle_preemption
            self._prev_sigterm = signal.signal(signal.SIGTERM,
                                               self._sigterm_handler)
            self._sigterm_installed = True
        except (ValueError, OSError):
            self._sigterm_installed = False

    def _handle_preemption(self, signum, frame) -> None:
        import signal
        # The handler interrupts the main thread at an arbitrary
        # bytecode — possibly INSIDE a non-reentrant critical section
        # (anomaly.observe holds AnomalyMonitor._lock every step).
        # Doing the dump/save work inline could then deadlock on a
        # lock this very thread holds, hanging the process through the
        # whole eviction grace — strictly worse than dying promptly.
        # So the work runs on a helper thread with a bounded join: in
        # the common case (signal lands in compute/sleep, locks free)
        # it completes fully; in the pathological case we give up
        # after the timeout and terminate — a mid-write save is left
        # torn, which restore detects and falls back from by design.
        t = threading.Thread(target=self.on_preemption,
                             args=(signum,),
                             name="parallax-preemption", daemon=True)
        t.start()
        t.join(timeout=30.0)
        if t.is_alive():
            parallax_log.error(
                "preemption dump/save did not finish within 30s "
                "(wedged on state the interrupted thread holds?); "
                "terminating without it")
        prev = self._prev_sigterm
        if callable(prev):
            prev(signum, frame)
        elif prev is signal.SIG_IGN:
            # the application had deliberately ignored SIGTERM; the
            # session may add its post-mortem/save on top but must not
            # convert an ignored signal into process death
            return
        else:
            # SIG_DFL (or an unknowable C-level disposition): restore
            # the default and re-deliver, so the process terminates
            # with the standard SIGTERM status the launcher/pod
            # runtime expects
            signal.signal(signum, signal.SIG_DFL)
            import os as _os
            _os.kill(_os.getpid(), signum)

    def _uninstall_preemption_handler(self) -> None:
        if not self._sigterm_installed:
            return
        import signal
        try:
            # only restore if the live handler is still OURS: with
            # overlapping session lifetimes, closing an older session
            # must neither strip a newer session's handler nor
            # reinstall a closed session's previous chain
            if signal.getsignal(signal.SIGTERM) \
                    is self._sigterm_handler:
                signal.signal(signal.SIGTERM,
                              self._prev_sigterm
                              if self._prev_sigterm is not None
                              else signal.SIG_DFL)
        except (ValueError, OSError, TypeError):
            pass
        self._sigterm_installed = False

    def step_flops(self, cheap_only: bool = True) -> Optional[float]:
        """XLA cost-analysis FLOPs of one compiled step, or None.
        ``cheap_only=True`` only reads an already-AOT-compiled
        executable (free); False allows a one-time re-trace+lower."""
        if self._engine is None:
            return None
        costs = self._engine.step_cost_analysis(cheap_only=cheap_only)
        flops = costs.get("flops")
        return float(flops) if flops else None

    def _ensure_flops(self, cheap_only: bool = True) -> None:
        """Attach FLOPs + device peak to the timeline once available,
        so per-step MFU appears in rows/goodput/dumps. Off the TPU
        the peak stays null — never fabricated; an unknown TPU kind
        raises (common/flops.device_peak_flops)."""
        if self._flops_resolved or self._engine is None:
            return
        flops = self.step_flops(cheap_only=cheap_only)
        if flops is None:
            return
        from parallax_tpu.common import flops as flops_lib
        dev = jax.devices()[0]
        peak = flops_lib.device_peak_flops(dev.platform,
                                           dev.device_kind)
        total_peak = peak * jax.device_count() if peak else None
        self.timeline.set_flops(flops, total_peak)
        self._flops_resolved = True

    def _goodput_for_dump(self) -> Dict:
        # cheap-only: a crash dump must not re-trace the model; with
        # warmup() used (the usual path) the AOT executable makes this
        # free, otherwise MFU just stays null in the artifact
        self._ensure_flops(cheap_only=True)
        return self.timeline.goodput()

    def _health_for_dump(self) -> Optional[Dict]:
        """Non-blocking health section: a flight dump must never hang
        on a wedged device draining pending readings."""
        if self.health is None:
            return None
        h = self.health
        return {
            "healthy": h.healthy,
            "first_nonfinite_step": h.first_nonfinite_step,
            "readings": h.recent_readings(),
        }

    def _config_summary(self) -> Dict:
        cfg = self._config
        import dataclasses as _dc
        return {
            "run_option": cfg.run_option,
            "sparse_grad_mode": cfg.sparse_grad_mode,
            "sync": cfg.sync,
            "shape_buckets": (list(cfg.shape_buckets)
                              if isinstance(cfg.shape_buckets,
                                            (list, tuple))
                              else cfg.shape_buckets),
            "prefetch_depth": cfg.prefetch_depth,
            "eager_fetch": cfg.eager_fetch,
            "monitor_health": cfg.monitor_health,
            "numerics_interval": cfg.numerics_interval,
            "numerics_drift_interval": cfg.numerics_drift_interval,
            "flight_dir": cfg.flight_dir,
            "flight_steps": cfg.flight_steps,
            "anomaly": _dc.asdict(cfg.anomaly_config),
            "num_workers": self.num_workers,
            "worker_id": self.worker_id,
        }

    def dump_flight(self, path: Optional[str] = None,
                    reason: str = "manual") -> str:
        """Write a flight-recorder post-mortem artifact NOW (the last
        ``Config.flight_steps`` steps' attribution rows, health
        readings, anomaly events, metrics snapshot, straggler report
        when taken) and return its path. Unlike the automatic incident
        triggers this works without ``Config.flight_dir`` (``path``
        defaults into it when set, else the CWD)."""
        return self.flight.dump(reason, path=path)

    def aggregate_host_steps(self, factor: float = 1.25) -> Dict:
        """COLLECTIVE (all processes must call): gather every host's
        recent step-time stats over the JAX coordinator channel and
        return the per-host table with any straggler NAMED
        (``obs/aggregate.py``). The report lands in subsequent flight
        dumps; a named straggler also counts into
        ``anomaly.stragglers`` and triggers a flight dump."""
        report = aggregate_lib.aggregate_host_step_times(
            self.timeline.local_stats(), factor=factor)
        self._last_host_report = report
        line = aggregate_lib.straggler_summary(report)
        if line is not None:
            self.metrics.counter("anomaly.stragglers").inc(
                len(report["stragglers"]))
            parallax_log.warning("%s", line)
            self.flight.trigger("straggler",
                                {"summary": line, "report": report})
        return report

    def ops_account(self) -> Optional[Dict[str, Any]]:
        """The run-lifetime goodput/badput account (obs/goodput.py):
        productive step time vs named badput classes, summing to wall
        clock by construction, cumulative across restart attempts.
        Embeds the per-step window partition. None when the obs layer
        is disabled (the ledger is structurally absent)."""
        if self.ledger is None:
            return None
        return self.ledger.account(self.timeline)

    # -- compile-ahead engine (compile/) ----------------------------------

    @_startup_entry
    def warmup(self, feed_dict: Optional[Dict[str, Any]] = None,
               batch_sizes: Optional[Sequence[int]] = None,
               background: bool = False):
        """AOT-compile the step for every declared batch bucket
        (``Config.shape_buckets``) — or explicit ``batch_sizes`` —
        ahead of step 0, so the first step of each bucket dispatches a
        ready executable instead of stalling on an XLA compile.

        ``feed_dict``: an example feed to build the engine from when it
        doesn't exist yet (equivalent to ``prepare(feed_dict)`` first).
        ``background=True`` runs the compiles on a daemon thread —
        overlapping warmup with data-pipeline startup — and returns the
        ``threading.Thread`` (``join()`` it, or just start stepping:
        steps the warmup hasn't reached yet take the normal jit path);
        otherwise blocks and returns {batch_size: compile_seconds}.
        """
        if feed_dict is not None:
            self.prepare(feed_dict)
        if self._engine is None:
            raise ValueError(
                "warmup needs an engine: pass feed_dict (or call "
                "prepare(example_feed)) first")
        if not background:
            t0w = time.perf_counter()
            with trace.span("session.warmup"):
                stats = self._engine.warmup(self._state, batch_sizes)
            if self.ledger is not None:
                # blocking AOT compiles are the canonical
                # compile/warmup badput (background warmup overlaps
                # data startup and stays off the critical path)
                self.ledger.note_badput("compile_warmup",
                                        time.perf_counter() - t0w)
            # the AOT executable makes cost-analysis FLOPs free: attach
            # them (and the chip peak) so per-step MFU starts flowing;
            # same for the compiled-memory account (obs/memwatch.py)
            self._ensure_flops(cheap_only=True)
            self.memwatch.capture_compiled(self._engine)
            return stats

        def _bg():
            try:
                with trace.span("session.warmup", background=True):
                    self._engine.warmup(self._state, batch_sizes)
                self._ensure_flops(cheap_only=True)
                self.memwatch.capture_compiled(self._engine)
            except Exception as e:  # warmup is an optimization: a
                # failure must never kill the training process
                parallax_log.warning("background warmup failed: %s", e)

        t = threading.Thread(target=_bg, name="parallax-warmup",
                             daemon=True)
        self._warmup_threads.append(t)
        t.start()
        return t

    def compile_stats(self) -> Dict[str, Any]:
        """JSON-ready compile/caching report (the tuner's and the
        cache's tests read it): declared bucket sizes, per-bucket AOT compile
        seconds, the executable-/engine-cache hit and miss
        counters, how often the model's loss was traced
        (``model_traces``) and, under ``jax``, what jax itself reported
        of this PROCESS's compiles (``compile/cache.CompileEvents``:
        seconds traced, lowered, and compiled or read back from the
        persistent cache; its requests, hits and misses)."""
        eng = self._engine
        return {
            "model_traces": self.metrics.counter(
                "engine.model_traces").value,
            "jax": {name[len("compile."):]: value for name, value in
                    compile_cache.compile_events.snapshot().items()},
            "shape_buckets": (list(eng._buckets)
                              if eng is not None and eng._buckets
                              else None),
            "warmup_compile_seconds": (
                {str(k): round(v, 3)
                 for k, v in sorted(eng.warmup_seconds.items())}
                if eng is not None else {}),
            "executable_cache": {
                "hits": self.metrics.counter(
                    "engine.executable_cache.hits").value,
                "misses": self.metrics.counter(
                    "engine.executable_cache.misses").value,
            },
            "engine_cache": {
                "hits": self.metrics.counter(
                    "session.engine_cache.hits").value,
                "misses": self.metrics.counter(
                    "session.engine_cache.misses").value,
            },
        }

    # -- online serving (serve/) ------------------------------------------

    def serve(self, infer_fn=None, program=None, **kw):
        """Put the live trained parameters behind a request queue: a
        :class:`~parallax_tpu.serve.session.ServeSession` sharing this
        session's mesh (no second mesh build), its parameter pytree
        (``state.params`` as-is — no host round trip) and its metrics
        registry (``serve.*`` lands next to ``pipeline.*``). Pass
        ``infer_fn(params, batch)`` for one-shot inference (plus
        ``example_feed=``) or ``program=`` for continuous decode;
        remaining kwargs forward to ``ServeSession``. Requires a built
        engine (``prepare(example_feed)`` or any step first). Serving
        knobs come from this session's
        ``Config.serve_config``. Close the serve session before this
        one."""
        from parallax_tpu.serve import ServeSession
        if self._engine is None:
            raise ValueError(
                "serve() needs a built engine: call "
                "prepare(example_feed) (or run a step) first")
        kw.setdefault("flight", self.flight)
        return ServeSession(infer_fn, self._state.params,
                            program=program, config=self._config,
                            mesh=self._engine.mesh, metrics=self.metrics,
                            **kw)

    def push_weights(self, fleet) -> dict:
        """Train -> serve continuous deployment (ISSUE 7): hot-swap
        this session's LIVE trained parameters into every replica of a
        :class:`~parallax_tpu.serve.fleet.ServeFleet`. The fleet
        rotates replicas out one at a time (drain -> swap -> re-admit),
        so traffic keeps flowing and — because the swap lands on each
        replica's existing mesh with the old leaves' shardings — the
        AOT signature sets survive: zero serve-time recompiles. The
        param pytree is passed as-is (device arrays; each replica
        ``device_put``\\ s onto its own placement). Returns the
        per-replica outcome map."""
        return fleet.push_weights(self._state.params)

    # -- partition search (reference: common/partitions.py) ---------------

    def _record_search_time(self, dt: float) -> None:
        self._step_times.append(dt)
        mesh_search = isinstance(self._search, MeshSearch)
        if mesh_search:
            warm, test = (self._search.trial_warmup,
                          self._search.trial_steps)
        else:
            warm = consts.NUM_ITERATIONS_FOR_WARMUP
            test = consts.NUM_ITERATIONS_FOR_TEST
        if len(self._step_times) < test:
            return
        if mesh_search:
            # median, not mean: mesh-search trial windows are short
            # (TuneConfig.trial_steps, default 12) and a single host
            # stall inside one would otherwise misrank near-tied
            # plans; the partition search keeps the reference's mean
            # over its 50-step window
            mean_t = float(np.median(self._step_times[warm:test]))
        else:
            mean_t = float(np.mean(self._step_times[warm:test]))
        self._step_times = []
        if jax.process_count() > 1:
            # All processes must take identical re-plan decisions (they
            # jit the same mesh), so agree on one timing: the average
            # across hosts, the reference's get_average_execution_time
            # (lib.py:211-256) without the socket protocol.
            from jax.experimental import multihost_utils
            mean_t = float(multihost_utils.process_allgather(
                np.asarray([mean_t])).mean())
        if mesh_search:
            nxt = self._search.report(self._plan, mean_t)
        else:
            nxt = self._search.report(
                mesh_lib.num_shards(self._engine.mesh), mean_t)
        if nxt is None:
            if mesh_search:
                best = self._search.best_plan()
                # the full decision record — candidates, per-trial
                # predicted-vs-measured, the winner's ratio — goes to
                # the flight recorder (provider + one-shot artifact)
                # and to callers via tune_summary()
                self._tune_result = self._search.summary()
                parallax_log.info(
                    "mesh search done: winner %s (%s)",
                    best.describe(), self._tune_result.get("winner"))
                if self.journal is not None:
                    s = self._tune_result
                    self.journal.emit(
                        "tune", "decision",
                        winner=s.get("winner"),
                        trials_measured=s.get("trials_measured"),
                        pruned_oom=s.get("pruned_oom"),
                        cost_basis=s.get("cost_basis"))
                    for refusal in (s.get("oom_refusals") or ()):
                        self.journal.emit(
                            "tune", "oom_refusal", severity="warning",
                            **({"plan": str(refusal)}
                               if not isinstance(refusal, dict)
                               else {k: refusal[k]
                                     for k in list(refusal)[:6]}))
                self.flight.trigger("tune_decision", self._tune_result)
                settled = (best.cache_key()
                           == self._plan.cache_key())
            else:
                best = self._search.best_partitions()
                parallax_log.info(
                    "partition search done: best num_partitions=%d",
                    best)
                settled = (best
                           == mesh_lib.num_shards(self._engine.mesh))
            self._search = None
            if not settled:
                # the winner was already built (and compiled, and
                # measured) as a candidate: _build_engine reuses it
                # from the engine cache
                self._build_engine_from_live(best)
            # the losing candidates' engines (and their executables)
            # are no longer reachable by any replan — free them
            dropped = self._engine_cache.prune(keep=self._engine)
            if dropped:
                parallax_log.info(
                    "auto-search: dropped %d losing candidate "
                    "engine(s) from the cache", dropped)
        else:
            parallax_log.info(
                "auto-search: trying %s",
                nxt.describe() if isinstance(nxt, Plan) else f"p={nxt}")
            self._build_engine_from_live(nxt)

    def _build_engine_from_live(self, plan_or_partitions) -> None:
        p = plan_or_partitions
        label = p.describe() if isinstance(p, Plan) else p
        with trace.span("partition.replan", plan=label):
            self._build_engine(self._last_example_batch, p)

    # -- feed/fetch conversion (session_context.py:179-233 parity) --------

    def _convert_feed(self, feed_dict):
        t0 = time.perf_counter()
        try:
            with trace.span("session.convert_feed"):
                return self._convert_feed_impl(feed_dict)
        finally:
            # per-thread: a prefetch-thread conversion (overlapped, off
            # the critical path) never lands in a dispatch-thread row
            self._phase.convert_s = time.perf_counter() - t0

    def _convert_feed_impl(self, feed_dict):
        batch = {}
        for name, value in feed_dict.items():
            if isinstance(value, (list, tuple)):
                if len(value) != self.num_replicas_per_worker:
                    raise ValueError(
                        f"feed {name!r}: got a list of {len(value)} arrays "
                        f"but num_replicas_per_worker="
                        f"{self.num_replicas_per_worker} (reference "
                        f"contract: one array per local replica)")
                value = np.concatenate([np.asarray(v) for v in value],
                                       axis=0)
            batch[name] = np.asarray(value)
        self._last_example_batch = batch
        return batch

    def _convert_fetch(self, fetches, outputs, lazy: bool = False,
                       step: Optional[int] = None):
        if lazy:
            def record(seconds, _step=step):
                self.pipeline_stats.record_blocked(seconds)
                if _step is not None:
                    # attribute the lazy materialization back to the
                    # step whose value it was (obs/timeline.py)
                    self.timeline.add_fetch_block(_step, seconds)
            wrap = lambda v: Fetch(v, record)  # noqa: E731
        else:
            wrap = _to_host
        if fetches is None:
            return {k: wrap(v) for k, v in outputs.items()}
        if isinstance(fetches, str):
            return wrap(self._one(fetches, outputs))
        return [wrap(self._one(f, outputs)) for f in fetches]

    def _one(self, name, outputs):
        if name not in outputs:
            raise KeyError(
                f"fetch {name!r} unknown; available: {sorted(outputs)}")
        return outputs[name]

    def _warn_sparse_overflow(self, where: str) -> None:
        """A user who never polls sparse_overflow_steps() must still hear
        that row_sparse_adagrad dropped updates (silent data corruption
        otherwise) — warn at every checkpoint and at close."""
        n = self.sparse_overflow_steps()
        if n > 0:
            parallax_log.warning(
                "row_sparse_adagrad overflowed max_touched_rows on %d "
                "step(s) so far (detected at %s): the lowest-activity "
                "rows of those steps' sparse updates were DROPPED. "
                "Raise max_touched_rows.", n, where)

    def close(self):
        # Each teardown step is isolated: a failure in one (a poisoned
        # device buffer surfacing in the overflow read or the health
        # drain, a failed async checkpoint commit raising from the
        # async-commit join) must not skip the rest — the sink thread would
        # run forever, an in-flight profiler trace would record
        # forever, the configured chrome trace would never land, and
        # engine.close() restores process-global jax settings later
        # sessions depend on.
        self._session_closed = True
        self._uninstall_preemption_handler()
        if self._prefetcher is not None:
            self._prefetcher.close()
            self._prefetcher = None
        for t in self._warmup_threads:
            # a background warmup still compiling must not race the
            # engine teardown below (it reads and writes engine state):
            # join unbounded — an XLA compile always terminates, and a
            # timed-out join would just resume the race the join
            # exists to prevent
            t.join()
        self._warmup_threads = []
        try:
            self._warn_sparse_overflow("close")
        except Exception as e:  # reads live opt_state: can race donation
            parallax_log.warning("sparse-overflow check failed: %s", e)
        if self.alerts is not None:
            try:
                # one final rule pass so a breach in the last
                # alert_interval_s still fires, then stop any daemon
                self.alerts.evaluate()
                self.alerts.stop()
            except Exception as e:
                parallax_log.warning("alert engine stop failed: %s", e)
        if self.journal is not None:
            try:
                self.journal.emit(
                    "session", "close", step=self._host_step,
                    goodput=(self.ledger.goodput_fraction()
                             if self.ledger is not None else None))
            except Exception as e:
                parallax_log.warning("journal close event failed: %s",
                                     e)
        try:
            self._ckpt.close()
        except Exception as e:  # e.g. a pending async save that failed
            parallax_log.warning("checkpoint close failed: %s", e)
        try:
            # stop an in-flight jax.profiler trace (a profile_range past
            # the last step would otherwise record forever)
            self._profile.close()
        except Exception as e:
            parallax_log.warning("profile close failed: %s", e)
        if self.health is not None:
            try:
                # drain every still-pending device value (blocking is
                # fine at close) so the report covers the whole run
                report = self.health.report()
                if not self.health.healthy:
                    parallax_log.warning("health at close: %s", report)
            except Exception as e:
                parallax_log.warning("health drain failed: %s", e)
        if self.numerics is not None:
            try:
                self.numerics.poll(block=True)
            except Exception as e:
                parallax_log.warning("numerics drain failed: %s", e)
        if self._metrics_sink is not None:
            try:
                self._metrics_sink.stop()  # writes the final JSONL line
            except Exception as e:
                parallax_log.warning("metrics sink stop failed: %s", e)
            self._metrics_sink = None
        if self._config.trace_path:
            try:
                path = trace.export_chrome_trace(self._config.trace_path)
                parallax_log.info("wrote chrome trace to %s", path)
            except Exception as e:  # e.g. unwritable path
                parallax_log.warning("chrome trace export failed: %s", e)
        if self._engine is not None:
            self._engine.close()


def _to_host(v):
    arr = np.asarray(v)
    return arr.item() if arr.ndim == 0 else arr


def _feed_nbytes(batch) -> int:
    """Per-step H2D volume: bytes of the converted host feed. Measured
    BEFORE feed_transforms (which run inside shard_batch), so a
    transform that pads or re-dtypes a feed shifts the true shipped
    volume off this number by the same factor on every step — the
    metric stays valid for trend/regression comparison."""
    return sum(int(getattr(leaf, "nbytes", 0))
               for leaf in jax.tree_util.tree_leaves(batch))
