"""User-facing configuration objects.

Schema-compatible with the reference's config tree
(reference: parallax/parallax/core/python/common/config.py:21-179) so a
Parallax user can carry their config code over, but every knob is given a
TPU-native meaning (documented per-field).  Knobs that are physically
meaningless on TPU (gRPC protocol selection, mpirun flags) are accepted and
recorded so existing call sites don't break, and surfaced via `.unused_knobs()`
for observability.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Sequence, Union

from parallax_tpu.common import consts


@dataclasses.dataclass
class PSConfig:
    """Sharded-parameter (reference: parameter-server) path options.

    Reference: config.py:21-49.

    * ``protocol``: kept for API parity. On TPU the sharded-variable data plane
      is XLA collectives over ICI/DCN, so this is recorded but unused.
    * ``replicate_variables``: reference mirrors PS variables onto each GPU
      (graph_transform_lib.py:584-704). TPU meaning: when True, *dense*
      variables are replicated over the mesh (the SPMD default); when False
      every divisible dense variable stays fully sharded (ZeRO-style) in
      HYBRID and is all-gathered where consumed (core/engine.py choose()).
    * ``local_aggregation``: two-stage sparse combine (reference:
      graph_transform_lib.py:1372-1556) — duplicate row gradients are
      segment-summed on the producing device before the cross-shard
      exchange, and the forward ships unique ids/rows only
      (ops/embedding.py _dedup_capacity). Exact; wire bytes shrink
      whenever duplicates are guaranteed (table rows < per-device ids).
    * ``dedup_capacity``: optional per-device unique-id slot count for
      the combine above. The automatic bound min(local ids, vocab+1)
      can't compress when the vocab is larger than a device's id list
      even though real batches (Zipf-distributed ids) still carry heavy
      duplication; declaring a smaller capacity ships only that many
      ids/rows. NEVER lossy: each lookup counts its distinct ids on
      device, and any step where some device overflows the declared
      capacity falls back (a mesh-uniform `lax.cond`) to the exact
      uncompressed exchange for that lookup — paying the full wire cost
      for that step instead of dropping updates.
    * ``cross_replica_sparse``: how row-sharded tables' gradients merge
      across the 'repl' mesh axis (the axis that crosses slices/DCN
      under the slice-aware mesh, core/mesh.py). None (default) picks
      per lookup by a static bytes model: a dense [rows/shard, dim]
      psum vs gathering only the deduped (ids, row-grads) over the
      whole mesh — the SPMD form of the reference shipping only
      aggregated (ids, values) over the slow network
      (graph_transform_lib.py:1372-1556). True/False forces the choice.
      Irrelevant when the mesh has a single 'repl' row.
    * ``boundary_among_servers`` / ``boundary_between_workers_and_servers``:
      reference op-placement heuristics that move cheap boundary ops across
      the worker<->ps cut (graph_transform_lib.py:1315-1370). On TPU, op
      placement inside the step is owned end-to-end by the XLA scheduler;
      these knobs are recorded but have no effect (reported by
      ``unused_knobs()`` when set off-default).
    """

    protocol: str = "grpc"
    replicate_variables: bool = True
    local_aggregation: bool = True
    # int: one capacity for every sharded lookup; dict: per-table
    # capacities — keys are parameter PATHS (e.g. {"emb": 768,
    # "softmax_w": 1792}; resolved in sparse_grad_mode="slices", where
    # the lookup identifies its table) or table SHAPE tuples (fallback;
    # beware same-shape tables collide). Input-id and label+candidate
    # lookups have very different distinct-id profiles, so per-table
    # declarations compress further at the same overflow margin.
    # Unlisted tables use the automatic exactness bound.
    dedup_capacity: Union[int, Dict[Any, int], None] = None
    cross_replica_sparse: Optional[bool] = None
    boundary_among_servers: bool = True
    boundary_between_workers_and_servers: bool = True


@dataclasses.dataclass
class MPIConfig:
    """Dense all-reduce path options (reference: config.py:51-69).

    ``mpirun_options`` is kept for parity; TPU launches use the JAX
    coordinator, not mpirun, so it is recorded but unused.
    """

    mpirun_options: str = ""


@dataclasses.dataclass
class CommunicationConfig:
    """Bundle of per-path comm options (reference: config.py:72-81)."""

    ps_config: PSConfig = dataclasses.field(default_factory=PSConfig)
    mpi_config: MPIConfig = dataclasses.field(default_factory=MPIConfig)


@dataclasses.dataclass
class CheckPointConfig:
    """Checkpointing (reference: config.py:84-99).

    Same triggering semantics as the reference's chief-only
    ``CheckpointSaverHook`` (lib.py:38-56): save every ``save_ckpt_steps``
    steps and/or every ``save_ckpt_secs`` seconds. On TPU the checkpoint
    is an atomic sharded save of the full train-state pytree
    (``parallax_tpu/ckpt/store.py``: per-process shard writes with
    per-shard checksums, manifest committed last — no chief bottleneck,
    no full-state gather, and a crash mid-save is DETECTED at restore
    and falls back to the previous complete checkpoint).
    """

    ckpt_dir: Optional[str] = None
    save_ckpt_steps: Optional[int] = None
    save_ckpt_secs: Optional[float] = None
    # Asynchronous saves (TPU-extra knob): the save copies the local
    # shards to host (the only critical-path cost, a bounded D2H
    # memcpy) and returns; serialization/fsync/commit run on a
    # background writer thread while training continues — the step
    # never blocks on storage. Bounded staleness: at most ONE save is
    # in flight (the next due save and close() join the previous
    # commit first; the wait is measured as ckpt.async_wait_seconds).
    # Default False = fully synchronous saves, matching the
    # reference's durability guarantee (a crash between an async
    # dispatch and its background commit loses that one save — opting
    # into the weaker guarantee is explicit; ADVICE r4). Validated
    # here — a misspelled knob raises instead of silently defaulting
    # off (it used to be read via getattr).
    async_save: bool = False
    # Retention/GC: keep the newest N COMPLETE checkpoints, delete
    # older ones (and torn directories older than the newest complete
    # one) after each commit. The reference kept everything
    # (max_to_keep=1000000, lib.py:44) — unbounded disk on a
    # long-running job; None opts back into that.
    max_to_keep: Optional[int] = 5

    def __post_init__(self):
        if self.save_ckpt_steps is not None \
                and int(self.save_ckpt_steps) < 1:
            raise ValueError(
                f"save_ckpt_steps must be >= 1, got "
                f"{self.save_ckpt_steps}")
        if self.save_ckpt_secs is not None \
                and float(self.save_ckpt_secs) <= 0:
            raise ValueError(
                f"save_ckpt_secs must be > 0, got "
                f"{self.save_ckpt_secs}")
        if self.max_to_keep is not None and int(self.max_to_keep) < 1:
            raise ValueError(
                f"max_to_keep must be >= 1 (or None to keep "
                f"everything), got {self.max_to_keep}")
        if not isinstance(self.async_save, bool):
            raise ValueError(
                f"async_save must be a bool, got "
                f"{self.async_save!r} — a truthy string here usually "
                f"means a config plumbing bug")


@dataclasses.dataclass
class RecoveryConfig:
    """NaN/divergence auto-recovery knobs (``parallax_tpu/ckpt/
    recovery.py``; no reference analogue — the reference dies on NaN).

    * ``enabled``: turn the policy on. Requires in-graph health
      outputs, so ``ParallaxConfig.monitor_health`` is auto-enabled;
      detection is step-granular, which costs the async pipeline's
      dispatch overlap (the dispatch thread blocks on each step's
      ``loss_finite`` scalar).
    * ``snapshot_every_steps``: cadence of the in-memory last-good
      snapshot (host copies of the addressable shards). Smaller =
      less lost work per rollback, more D2H copies.
    * ``max_retries``: CONSECUTIVE non-finite steps tolerated (each
      one rolls back and skips its batch) before the run surrenders
      with a ``recovery_surrender`` flight dump and raises
      :class:`~parallax_tpu.ckpt.recovery.RecoverySurrender`.
    """

    enabled: bool = False
    snapshot_every_steps: int = 25
    max_retries: int = 3

    def __post_init__(self):
        if int(self.snapshot_every_steps) < 1:
            raise ValueError(
                f"snapshot_every_steps must be >= 1, got "
                f"{self.snapshot_every_steps}")
        if int(self.max_retries) < 1:
            raise ValueError(
                f"max_retries must be >= 1, got {self.max_retries}")


@dataclasses.dataclass
class ProfileConfig:
    """Step-bracketed profiling (reference: config.py:101-117).

    Reference captures ``RunMetadata`` with FULL_TRACE on the configured
    steps (session_context.py:74-92). TPU meaning: ``jax.profiler`` trace
    (XPlane) captured on those steps, one collector per host;
    ``profile_worker`` selects which host captures (CUPTI's one-profiler-per-
    machine restriction has no TPU analogue but the gating is kept so traces
    aren't duplicated N times).
    """

    profile_dir: Optional[str] = None
    profile_steps: Optional[Sequence[int]] = None
    profile_range: Optional[Sequence[int]] = None  # (begin, end) step range
    profile_worker: Optional[int] = None


@dataclasses.dataclass
class AnomalyConfig:
    """Knobs of the online anomaly detectors (``obs/anomaly.py``,
    no reference analogue).

    A *spike* is one observation far above the rolling baseline
    (robust median/MAD test); a *shift* is a sustained level change —
    the change-point case a single-outlier test misses (a step-time
    regression, not a blip). Both count into ``anomaly.*`` and trigger
    a flight-recorder dump when ``flight_dir`` is configured.

    * ``enabled``: master switch (the obs kill switch also disables).
    * ``window``: rolling baseline sample count per signal.
    * ``min_samples``: observations before detection arms — compiles
      and warmup steps land in the baseline, never fire it.
    * ``spike_mads``: a spike must exceed the median by this many
      (scaled) MADs…
    * ``spike_min_ratio``: …AND by this multiplicative ratio (keeps a
      near-constant signal, MAD ~ 0, from firing on microscopic
      jitter).
    * ``shift_window`` / ``shift_ratio``: a shift fires when the mean
      of the last ``shift_window`` observations exceeds ``shift_ratio``
      × the older window's median; the detector then rebaselines.
    * ``cooldown``: observations before the same signal may fire again.
    """

    enabled: bool = True
    window: int = 64
    min_samples: int = 16
    spike_mads: float = 8.0
    spike_min_ratio: float = 2.0
    shift_window: int = 8
    shift_ratio: float = 1.5
    cooldown: int = 32

    def __post_init__(self):
        if int(self.window) < 2:
            raise ValueError(
                f"anomaly window must be >= 2, got {self.window}")
        if int(self.min_samples) < 2:
            raise ValueError(
                f"anomaly min_samples must be >= 2, got "
                f"{self.min_samples}")
        # arming requires min_samples observations IN the window, and
        # the shift test needs shift_window more on top — a config
        # violating either would be a silent no-op detector
        if int(self.window) < int(self.min_samples):
            raise ValueError(
                f"anomaly window ({self.window}) must be >= "
                f"min_samples ({self.min_samples}); detection would "
                f"never arm")
        if int(self.window) < int(self.min_samples) \
                + max(2, int(self.shift_window)):
            raise ValueError(
                f"anomaly window ({self.window}) must be >= "
                f"min_samples + shift_window "
                f"({self.min_samples} + {self.shift_window}); the "
                f"shift (change-point) detector would never arm")
        for name in ("spike_mads", "spike_min_ratio", "shift_ratio"):
            if float(getattr(self, name)) <= 0:
                raise ValueError(
                    f"anomaly {name} must be > 0, got "
                    f"{getattr(self, name)}")


@dataclasses.dataclass
class TuneConfig:
    """Auto-tuner v2 knobs (``parallax_tpu.tune``, ISSUE 10): the
    cost-model-driven search over ``(dp x tp)`` mesh shapes crossed
    with run options. ``Config(tune_config=TuneConfig())`` routes the
    session's planning through :class:`~parallax_tpu.tune.search.
    MeshSearch`; ``tune_config=None`` (default) keeps the legacy 1-D
    ``PartitionSearch`` behavior.

    * ``enabled``: master switch (a constructed-but-disabled config
      documents intent without changing planning).
    * ``top_k``: how many cost-model-shortlisted plans pay a MEASURED
      trial; everything else is priced analytically only.
    * ``run_options``: the run-option axis of the search space
      (default: AR, SHARD and HYBRID; legacy MPI/PS aliases accepted).
    * ``min_tp`` / ``max_tp``: bounds on the shard-axis width
      candidates (divisors of the device count within the range).
    * ``max_pp``: cap on the pipeline-stage axis (ISSUE 18). The
      default 1 keeps the search exactly 2-D; ``max_pp > 1`` admits
      ``pp > 1`` plans — but only for models that declare
      ``Model.pipeline_info`` (the schedule, microbatch count and
      layer stack the stages would split), so the knob is inert on
      non-pipeline models.
    * ``trial_steps`` / ``trial_warmup``: steps per measured trial;
      the MEDIAN over steps ``[trial_warmup, trial_steps)`` is the
      trial's time (robust to a single host stall inside the short
      window; the partition search keeps the reference's mean over
      its 100-step windows — which would dwarf the whole point of
      the cost-model prune here).
    * ``peak_flops`` / ``hbm_gbps`` / ``ici_gbps``: cost-model
      constant overrides (per device; GB/s for the bandwidths). Unset,
      the model resolves the chip's published peak where known and
      otherwise falls back to nominal TPU-class constants — rankings
      stay meaningful, absolute predictions are CPU-relative.
    * ``hbm_budget_gb`` / ``hbm_headroom``: the OOM preflight
      (``obs/memwatch.py``, ISSUE 13). Any shortlisted plan whose
      compiled ``memory_analysis()`` peak exceeds
      ``budget x headroom`` is REFUSED before paying a measured
      trial, recorded in the decision record like
      ``pruned_equivalent``. ``hbm_budget_gb`` unset resolves the
      budget from the smallest ``bytes_limit`` a local device
      reports; backends reporting neither (the CPU rig) skip the
      preflight — refusal requires evidence, never a guess.
    """

    enabled: bool = True
    top_k: int = 3
    run_options: Optional[Sequence[str]] = None
    min_tp: int = 1
    max_tp: Optional[int] = None
    max_pp: int = 1
    trial_steps: int = 12
    trial_warmup: int = 4
    peak_flops: Optional[float] = None
    hbm_gbps: Optional[float] = None
    ici_gbps: Optional[float] = None
    hbm_budget_gb: Optional[float] = None
    hbm_headroom: float = 0.9

    def __post_init__(self):
        if int(self.top_k) < 1:
            raise ValueError(
                f"tune top_k must be >= 1, got {self.top_k}")
        if self.run_options is not None:
            opts = tuple(normalize_run_option(o)
                         for o in self.run_options)
            if not opts:
                raise ValueError(
                    "tune run_options must name at least one of "
                    "AR/SHARD/HYBRID (or be None for all three)")
            # dedupe, order preserved (the order breaks score ties)
            self.run_options = tuple(dict.fromkeys(opts))
        if int(self.min_tp) < 1:
            raise ValueError(
                f"tune min_tp must be >= 1, got {self.min_tp}")
        if self.max_tp is not None and int(self.max_tp) < int(self.min_tp):
            raise ValueError(
                f"tune max_tp ({self.max_tp}) must be >= min_tp "
                f"({self.min_tp})")
        if int(self.max_pp) < 1:
            raise ValueError(
                f"tune max_pp must be >= 1, got {self.max_pp}")
        if int(self.trial_warmup) < 0:
            raise ValueError(
                f"tune trial_warmup must be >= 0, got "
                f"{self.trial_warmup}")
        if int(self.trial_steps) <= int(self.trial_warmup):
            raise ValueError(
                f"tune trial_steps ({self.trial_steps}) must exceed "
                f"trial_warmup ({self.trial_warmup}); the measured "
                f"window would be empty")
        for name in ("peak_flops", "hbm_gbps", "ici_gbps",
                     "hbm_budget_gb"):
            v = getattr(self, name)
            if v is not None and float(v) <= 0:
                raise ValueError(
                    f"tune {name} must be > 0 when set, got {v}")
        if not (0.0 < float(self.hbm_headroom) <= 1.0):
            raise ValueError(
                f"tune hbm_headroom must be in (0, 1], got "
                f"{self.hbm_headroom}")


@dataclasses.dataclass
class ServeConfig:
    """Online-serving knobs (``parallax_tpu.serve``, no reference
    analogue — the reference is training-only).

    * ``max_batch``: upper bound on requests fused into one device
      batch; also the slot count of the continuous-decode scheduler.
    * ``max_wait_ms``: batch-formation deadline — a partially filled
      batch dispatches once the OLDEST waiting request has aged this
      long (latency bound), instead of waiting for ``max_batch``
      (throughput bound). 0 dispatches whatever is queued immediately.
    * ``max_queue``: admission bound. A submit beyond this many waiting
      requests is SHED (``ServeOverloaded`` raised to the caller,
      ``serve.shed`` counted) — bounded memory and bounded worst-case
      queueing delay instead of silent collapse under overload.
    * ``default_deadline_ms``: per-request latency budget when the
      caller doesn't pass one. A request whose deadline expires before
      it is dispatched is dropped (``DeadlineExceeded`` on its future,
      ``serve.timeouts`` counted) — never compute a result nobody is
      waiting for. None = no deadline.
    * ``batch_buckets``: declared batch sizes formed batches are padded
      up to (the compile/ bucketing rule applied to serving); default
      powers of two up to ``max_batch``. Together with
      ``length_buckets`` this is the COMPLETE signature set the session
      AOT-compiles at startup — live traffic never recompiles.
    * ``length_buckets``: sequence-length buckets for ragged per-request
      feeds (declared via ``ServeSession(ragged_feeds=...)``); each
      request's ragged feeds are padded to the smallest bucket that
      fits its longest one. None = requests must share fixed shapes.
    * ``drain_timeout_s``: ``close()`` stops admission and serves the
      already-accepted queue to completion, up to this long; whatever
      is still queued after it is failed with ``ServeClosed``.
    * ``prefix_cache``: enable prefix-aware KV reuse (ISSUE 15,
      serve/prefixcache.py) on the paged continuous-decode path:
      finished sequences are indexed by token prefix in a per-tenant
      radix cache, identical requests replay cached tokens and map the
      cached pages read-only (copy-on-write at the divergence
      boundary), and pool exhaustion evicts LRU unpinned cached
      prefixes before deferring. Requires a paged program; ignored by
      one-shot sessions.
    * ``prefix_cache_max_pages``: bound on pool pages the prefix cache
      may hold (best effort — pinned entries are never evicted);
      None = bounded only by pool-exhaustion eviction.
    * ``prefix_cache_max_entries``: bound on cached ENTRIES. Each
      entry also pins its prefill request state — device arrays the
      page accounting cannot see (for the NMT adapter,
      ``2 * num_layers * max_src_len * model_dim`` cross-K/V values
      per entry) — so workloads with long sources and short decodes
      should cap entries, not just pages. None = unbounded count.
    * ``tenant_quotas`` / ``default_tenant_quota``: per-tenant
      admission quotas — a tenant's admitted-but-unfinished requests
      are capped at its quota (``tenant_quotas[tenant]``, else
      ``default_tenant_quota``, else unlimited), shed with
      ``TenantQuotaExceeded`` (a retryable ``ServeOverloaded``). The
      cap is also the fairness floor: a noisy tenant cannot consume
      the capacity other tenants' quotas entitle them to.
    * ``slo_classes``: named service classes, ``{name: {"priority":
      int, "deadline_ms": float | None}}``. ``submit(slo_class=...)``
      requests inherit the class deadline when the caller passes
      none; in CONTINUOUS-DECODE mode the queue additionally serves
      lower priority ranks first (FIFO within a class). One-shot
      batch formation stays FIFO/group-keyed — there the class
      contributes its deadline only. Unknown class names are refused
      at submit.
    """

    max_batch: int = 8
    max_wait_ms: float = 5.0
    max_queue: int = 128
    default_deadline_ms: Optional[float] = None
    batch_buckets: Optional[Sequence[int]] = None
    length_buckets: Optional[Sequence[int]] = None
    drain_timeout_s: float = 30.0
    prefix_cache: bool = False
    prefix_cache_max_pages: Optional[int] = None
    prefix_cache_max_entries: Optional[int] = None
    tenant_quotas: Optional[Dict[Any, int]] = None
    default_tenant_quota: Optional[int] = None
    slo_classes: Optional[Dict[str, Dict[str, Any]]] = None

    def __post_init__(self):
        if int(self.max_batch) < 1:
            raise ValueError(
                f"serve max_batch must be >= 1, got {self.max_batch}")
        if float(self.max_wait_ms) < 0:
            raise ValueError(
                f"serve max_wait_ms must be >= 0, got {self.max_wait_ms}")
        if int(self.max_queue) < 1:
            raise ValueError(
                f"serve max_queue must be >= 1, got {self.max_queue}")
        if self.default_deadline_ms is not None \
                and float(self.default_deadline_ms) <= 0:
            raise ValueError(
                f"serve default_deadline_ms must be > 0, got "
                f"{self.default_deadline_ms}")
        for name in ("batch_buckets", "length_buckets"):
            v = getattr(self, name)
            if v is None:
                continue
            v = tuple(sorted({int(b) for b in v}))
            if not v or any(b < 1 for b in v):
                raise ValueError(
                    f"serve {name} must be positive sizes, got "
                    f"{getattr(self, name)!r}")
            setattr(self, name, v)
        if self.batch_buckets is not None \
                and self.batch_buckets[-1] < int(self.max_batch):
            raise ValueError(
                f"serve batch_buckets {self.batch_buckets} do not cover "
                f"max_batch={self.max_batch}; the largest bucket must "
                f"fit a full batch")
        for name in ("prefix_cache_max_pages",
                     "prefix_cache_max_entries"):
            v = getattr(self, name)
            if v is not None and int(v) < 0:
                raise ValueError(
                    f"serve {name} must be >= 0, got {v}")
        for name, q in (self.tenant_quotas or {}).items():
            if int(q) < 1:
                raise ValueError(
                    f"serve tenant quota for {name!r} must be >= 1, "
                    f"got {q}")
        if self.default_tenant_quota is not None \
                and int(self.default_tenant_quota) < 1:
            raise ValueError(
                f"serve default_tenant_quota must be >= 1, got "
                f"{self.default_tenant_quota}")
        for name, cls in (self.slo_classes or {}).items():
            if not isinstance(cls, dict) or "priority" not in cls:
                raise ValueError(
                    f"serve slo_classes[{name!r}] must be a dict with "
                    f"a 'priority' key, got {cls!r}")
            ddl = cls.get("deadline_ms")
            if ddl is not None and float(ddl) <= 0:
                raise ValueError(
                    f"serve slo_classes[{name!r}] deadline_ms must be "
                    f"> 0 or None, got {ddl}")

    def resolve_slo_class(self, name: Optional[str]):
        """``(priority_rank, class_deadline_ms)`` for an SLO class
        name (rank 0 / no deadline for None); unknown names are
        refused loudly — a typo'd class silently served best-effort
        would be an SLO hole."""
        if name is None:
            return 0, None
        classes = self.slo_classes or {}
        if name not in classes:
            raise ValueError(
                f"unknown slo_class {name!r}; declared: "
                f"{sorted(classes) or '(none)'}")
        cls = classes[name]
        ddl = cls.get("deadline_ms")
        return int(cls["priority"]), (float(ddl) if ddl is not None
                                      else None)

    def resolved_batch_buckets(self) -> tuple:
        """Declared buckets, or doubling sizes 1,2,4,... up to (and
        including) ``max_batch``."""
        if self.batch_buckets is not None:
            return tuple(self.batch_buckets)
        out, b = [], 1
        while b < int(self.max_batch):
            out.append(b)
            b *= 2
        out.append(int(self.max_batch))
        return tuple(out)


@dataclasses.dataclass
class ParallaxConfig:
    """Top-level config (reference: config.py:119-179).

    * ``run_option``: 'AR' | 'SHARD' | 'HYBRID' (legacy aliases
      'MPI' | 'PS' | 'HYBRID' accepted). HYBRID routes each variable to the
      cheaper path: dense -> replicate + all-reduce grads, sparse -> row-shard
      + all-to-all row updates (reference: runner.py:93-119).
    * ``average_sparse``: average duplicate sparse row updates by occurrence
      count instead of summing (reference fork's SPARSE_AVERAGE_BY_COUNTER,
      graph_transform_lib.py:101-102) -> segment-mean vs segment-sum.
    * ``sess_config``: accepted for parity (TF session config); unused.
    * ``redirect_path``: per-process stdout/stderr redirect dir.
    * ``search_partitions``: enable the partition auto-search loop
      (reference: partitions.py:53-170).
    * ``export_graph_path``: reference dumps the transformed MetaGraph text
      (lib.py:258-264); we dump the compiled step's HLO / StableHLO text.
    * ``debug_nans``: enable jax_debug_nans for the session — compiled
      steps re-run op-by-op on a NaN and raise at the producing op (a
      numerics-sanitizer capability the reference lacks, SURVEY.md §5.2).
    * ``sparse_grad_mode``: how table gradients are represented.
      'dense' (default): AD scatter-adds row cotangents into a dense
      [V, D] array (simple, works with any optax optimizer).
      'slices': for tables registered in ``Model.slice_updaters``, the
      engine captures (ids, row-grad) pairs at the lookup sites and
      applies them scatter-only — TF IndexedSlices semantics, exactly
      how the reference applies sparse grads (outside the global-norm
      clip, straight into the sparse optimizer kernel; reference
      examples/lm1b/language_model_graph.py:48-58). No [V, D] cotangent,
      accumulator pass, or table-grad norm is ever materialized.
    * ``prefetch_depth`` / ``eager_fetch``: async step pipeline knobs
      (no reference analogue — the reference's tf.data input pipeline
      owned this); see the field comments and session.py.
    * ``shape_buckets`` / ``bucket_mask_feed`` /
      ``compilation_cache_dir``: the compile-ahead engine (compile/) —
      batch-shape bucketing, AOT warmup and executable/compilation
      caching; see the field comments and compile/__init__.py.
    * ``trace_path`` / ``metrics_path`` / ``metrics_interval_s`` /
      ``monitor_health`` / ``log_level`` / ``log_json``: the unified
      observability layer (obs/) — always-on span tracing + metrics
      registry + opt-in health monitors; no reference analogue (the
      reference's only windows were per-step RunMetadata dumps and the
      Horovod timeline). See the field comments and obs/__init__.py.
    """

    run_option: str = consts.RUN_HYBRID
    sparse_grad_mode: str = "dense"
    # -- async step pipeline (session.py) --------------------------------
    # Bounded depth of the background feed prefetcher behind
    # ``session.run_iter`` / ``data.prefetch_to_device``: how many
    # converted-and-placed batches may exist ahead of the step consuming
    # them. 2 keeps one batch in flight on the H2D path while one waits,
    # bounding host+HBM staging memory; raise it only when feed prep has
    # high variance.
    prefetch_depth: int = 2
    # -- compile-ahead engine (compile/) ---------------------------------
    # Batch-shape buckets: ascending batch sizes every feed batch is
    # padded up to (smallest bucket that fits), or "auto" (= the first
    # batch's size, covering the classic ragged final tail). Padded
    # rows get the mask feed zeroed so a weight-normalized loss stays
    # exact; full batches pass through bit-identical. None (default) =
    # no bucketing: every new batch shape retraces the step (counted by
    # engine.recompiles).
    shape_buckets: Union[None, str, Sequence[int]] = None
    # The per-example weight feed bucketing masks: an existing feed of
    # this name (e.g. lm1b's "w") has its padded rows zeroed; when
    # absent, a [bucket] float32 mask (1=real, 0=padding) is added
    # under this name on every batch so the feed structure stays
    # signature-stable.
    bucket_mask_feed: str = "w"
    # A fixed directory for JAX's persistent compilation cache:
    # repeated launches of the same program skip XLA entirely
    # (compiles become disk reads). Process-global; keyed by HLO +
    # compile environment, so a stale cache can only miss, never
    # corrupt. Honoured only when JAX_COMPILATION_CACHE_DIR is unset
    # (a cache placed from outside wins); None = <checkout>/.jax_cache
    # (compile/cache.ensure_persistent_cache decides).
    compilation_cache_dir: Optional[str] = None
    # When True, ``run()`` materializes every fetch to a host value
    # before returning (the pre-async blocking behavior). Default False:
    # fetches come back as lazy ``Fetch`` handles and the host thread is
    # free to prepare batch t+1 while step t runs. Profiling steps and
    # the partition search always block regardless, so their wall-times
    # cover real device work.
    eager_fetch: bool = False
    # -- observability (obs/) --------------------------------------------
    # Chrome trace-event JSON written at session close: the host-side
    # span timeline of the dispatch / prefetch / fetch threads, openable
    # in chrome://tracing or Perfetto. None = no export (spans still
    # collect into the bounded ring buffer; obs.export_chrome_trace()
    # can dump it any time). The collector is PROCESS-global — the
    # export is the one-view timeline of everything the process did
    # (including other sessions), not a per-session slice.
    trace_path: Optional[str] = None
    # Ring-buffer capacity (events) of the span collector; old events
    # fall off. ~100 bytes/event, so the default is a few MB. Grow-only
    # against the process-global collector: a later session with a
    # smaller value never truncates a ring an earlier session sized up.
    trace_buffer_events: int = 65536
    # JSONL file appended by a background sink every metrics_interval_s
    # seconds (plus once at close): one `{"ts": ..., "metrics":
    # registry.snapshot()}` line per tick, for machine scraping of live
    # runs. None = no sink (snapshot() is always available in-process).
    metrics_path: Optional[str] = None
    metrics_interval_s: float = 10.0
    # Size bound for the JSONL sink file: when an append would cross
    # it, the file rotates to `<metrics_path>.1` (replacing a previous
    # rotation) with a loud warning — a long-lived serving fleet must
    # not fill the disk. None (default) = historical unbounded growth.
    metrics_max_bytes: Optional[int] = None
    # Opt-in per-step health monitoring: the engine appends in-graph
    # `loss_finite` / `grad_norm` outputs (a few FLOPs next to the
    # backward pass) and the session consumes them LAZILY — only values
    # whose D2H transfer already finished are read, so the async
    # pipeline never blocks on monitoring. Non-finite values warn
    # immediately and count into the registry (health.*).
    monitor_health: bool = False
    # Numerics observatory (obs/numwatch.py): every N steps the engine
    # appends one fused in-graph per-layer stats reduction (grad/param
    # norm, absmax, non-finite count, bf16 underflow fraction, update
    # ratio — per param-tree prefix) to the step outputs, consumed
    # lazily like monitor_health into `numerics.<layer>.*` gauges, a
    # forensics trail, and anomaly feeds. The sample is FORCED on any
    # non-finite loss/grad step, so the nonfinite_rollback artifact can
    # name the first poisoned layer (NaN provenance). 0 (default) =
    # off: no extra step outputs, no monitor constructed. > 0
    # auto-enables monitor_health (provenance needs loss_finite).
    numerics_interval: int = 0
    # Kernel-drift sentinels (obs/numwatch.py DriftSentinel): every N
    # HOST steps the session shadow-evals each hand-built Pallas
    # executor against its reference (LSTM bwd kernel vs scan,
    # paged-attn kernel vs einsum) and exports rel-error / argmax-flip
    # gauges. Each sweep runs both executors on the dispatch thread —
    # whole milliseconds, not micros — so the default 0 keeps it out
    # of the training loop; tests run the sentinels explicitly.
    numerics_drift_interval: int = 0
    # Override the PARALLAX logger level for this run (default: leave
    # the env-var/import-time level alone). E.g. "DEBUG", "WARNING".
    log_level: Optional[str] = None
    # Re-format PARALLAX log lines as one JSON object per line (ts /
    # level / logger / msg) for machine-scraped runs.
    log_json: bool = False
    # -- training forensics (obs/timeline, flightrec, anomaly) -----------
    # Directory for flight-recorder auto-dumps: on a crash escaping a
    # step, a non-finite loss (monitor_health=True), a serve SLO
    # breach, or an anomaly firing, the session writes one JSON
    # post-mortem artifact (last flight_steps timeline rows, health
    # readings, anomaly events, metrics snapshot) there. None (default)
    # disables auto-dumps — the bounded history still collects and
    # session.dump_flight(path) works any time.
    flight_dir: Optional[str] = None
    # Ring capacity of the per-step timeline (and so of the flight
    # recorder's step log): the last N steps' attribution rows are
    # always available. ~200 bytes/row.
    flight_steps: int = 256
    # Online anomaly detection (step-time spikes/shifts, loss and
    # grad-norm spikes — the latter two only with monitor_health=True).
    # See the AnomalyConfig docstring.
    anomaly_config: "AnomalyConfig" = dataclasses.field(
        default_factory=lambda: AnomalyConfig())
    # -- ops observatory (obs/journal, obs/goodput, obs/alerts) ----------
    # JSONL file the event journal appends one line per lifecycle
    # event to (anomalies, rollbacks, ckpt save/restore, preemption,
    # fleet churn, tuner decisions, alert firings). None (default) =
    # in-memory ring only; the ring tail still rides in flight dumps.
    journal_path: Optional[str] = None
    # Ring capacity (events) of the in-memory journal — the recent
    # causal history flight dumps embed. ~200 bytes/event.
    journal_capacity: int = 512
    # Size bound for the journal JSONL file: rotates to `<path>.1`
    # (like metrics_max_bytes). None = unbounded growth.
    journal_max_bytes: Optional[int] = None
    # Alert-evaluation cadence (seconds): the session polls the alert
    # engine from the step loop (one clock compare per step; a full
    # rule pass only every alert_interval_s). The engine itself exists
    # whenever the obs layer is enabled — disabling obs removes it
    # structurally (no rules, no state, no thread).
    alert_interval_s: float = 30.0
    # Extra AlertRules armed next to the builtins (SLO burn,
    # instability, serve recompiles, page-pool exhaustion,
    # goodput-below-floor). See obs/alerts.py.
    alert_rules: Sequence[Any] = ()
    # Threshold for the goodput-below-floor builtin rule; the rule is
    # guarded on >= 120s of run wall so short runs never fire it.
    goodput_floor: float = 0.5
    # sync=False only: gradient staleness bound k — each step applies
    # the gradients computed k steps earlier (deterministic SPMD
    # emulation of the reference's async PS, whose staleness was
    # unbounded). Costs k extra parameter-sized buffers.
    staleness: int = 1
    average_sparse: bool = False
    sess_config: Any = None
    redirect_path: Optional[str] = None
    search_partitions: bool = True
    export_graph_path: Optional[str] = None
    debug_nans: bool = False
    communication_config: CommunicationConfig = dataclasses.field(
        default_factory=CommunicationConfig)
    ckpt_config: CheckPointConfig = dataclasses.field(
        default_factory=CheckPointConfig)
    profile_config: ProfileConfig = dataclasses.field(
        default_factory=ProfileConfig)
    # NaN/divergence auto-recovery (ckpt/recovery.py): in-memory
    # last-good snapshot + rollback + batch skip + bounded retries.
    # enabled=True auto-enables monitor_health (the policy needs the
    # in-graph loss_finite/grad_norm outputs). See RecoveryConfig.
    recovery_config: "RecoveryConfig" = dataclasses.field(
        default_factory=lambda: RecoveryConfig())
    # Preemption handling: when a SIGTERM (the eviction notice on
    # preemptible pods) reaches a session-owning process, dump a
    # `preemption` flight artifact and attempt one final synchronous
    # checkpoint save before terminating. Installed only on the main
    # thread and only when flight_dir or ckpt_dir is configured;
    # restored at session close.
    handle_preemption: bool = True
    # -- online serving (serve/) -----------------------------------------
    # Dynamic micro-batching / continuous-decode knobs for
    # ``parallax_tpu.serve.ServeSession`` (batch formation under
    # (max_batch, max_wait_ms), admission control + load shedding,
    # per-request deadlines, the AOT-warmed signature set). See the
    # ServeConfig docstring and docs/parallax_api.md "Serving".
    serve_config: ServeConfig = dataclasses.field(
        default_factory=ServeConfig)
    # -- auto-tuner v2 (tune/) -------------------------------------------
    # Cost-model-driven search over (dp x tp) mesh shapes and run
    # options (ISSUE 10). None (default) = legacy planning: the
    # config's run_option + num_partitions / the 1-D PartitionSearch.
    # A TuneConfig routes session planning through tune.MeshSearch:
    # the full plan space is priced analytically and only the top_k
    # shortlist pays measured trials. See the TuneConfig docstring.
    tune_config: Optional["TuneConfig"] = None
    # Cost-model calibration file (tune/calibrate.py, ISSUE 13): when
    # set and readable, the cost model divides each roofline term by
    # the file's measured predicted/measured ratio instead of trusting
    # nominal constants; session.write_calibration() creates/refreshes
    # it from a profiled window (session.profile_steps). Missing or
    # corrupt files fall back to nominal, loudly. The ratios are
    # rig-relative — do not ship a CPU-made file to a TPU pod.
    calibration_path: Optional[str] = None

    # Injected by parallel_run, mirroring the reference's set_sync /
    # set_resource_info setters (config.py:168-179).
    sync: bool = True
    resource_info: Any = None

    def __post_init__(self):
        self.run_option = normalize_run_option(self.run_option)
        if self.recovery_config.enabled and not self.monitor_health:
            # the policy consumes the in-graph loss_finite/grad_norm
            # outputs; declaring recovery IS declaring health intent
            self.monitor_health = True
        if int(self.numerics_interval) < 0:
            raise ValueError(
                f"numerics_interval must be >= 0, got "
                f"{self.numerics_interval}")
        if int(self.numerics_drift_interval) < 0:
            raise ValueError(
                f"numerics_drift_interval must be >= 0, got "
                f"{self.numerics_drift_interval}")
        if self.numerics_interval > 0 and not self.monitor_health:
            # provenance keys off the loss_finite trip and the trail
            # rides the same lazy-consumption cadence
            self.monitor_health = True
        if self.sparse_grad_mode not in ("dense", "slices"):
            raise ValueError(
                f"sparse_grad_mode must be 'dense' or 'slices', got "
                f"{self.sparse_grad_mode!r}")
        if int(self.staleness) < 1:
            raise ValueError(
                f"staleness must be >= 1, got {self.staleness}")
        if int(self.prefetch_depth) < 1:
            raise ValueError(
                f"prefetch_depth must be >= 1, got {self.prefetch_depth}")
        if float(self.metrics_interval_s) <= 0:
            raise ValueError(
                f"metrics_interval_s must be > 0, got "
                f"{self.metrics_interval_s}")
        if self.metrics_max_bytes is not None \
                and int(self.metrics_max_bytes) <= 0:
            raise ValueError(
                f"metrics_max_bytes must be > 0 or None, got "
                f"{self.metrics_max_bytes}")
        if int(self.trace_buffer_events) < 1:
            raise ValueError(
                f"trace_buffer_events must be >= 1, got "
                f"{self.trace_buffer_events}")
        if int(self.flight_steps) < 1:
            raise ValueError(
                f"flight_steps must be >= 1, got {self.flight_steps}")
        if int(self.journal_capacity) < 1:
            raise ValueError(
                f"journal_capacity must be >= 1, got "
                f"{self.journal_capacity}")
        if self.journal_max_bytes is not None \
                and int(self.journal_max_bytes) <= 0:
            raise ValueError(
                f"journal_max_bytes must be > 0 or None, got "
                f"{self.journal_max_bytes}")
        if float(self.alert_interval_s) <= 0:
            raise ValueError(
                f"alert_interval_s must be > 0, got "
                f"{self.alert_interval_s}")
        if not (0.0 <= float(self.goodput_floor) <= 1.0):
            raise ValueError(
                f"goodput_floor must be in [0, 1], got "
                f"{self.goodput_floor}")
        if self.shape_buckets is not None:
            # one validation rule, owned by compile/bucketing.py (the
            # lazy import keeps config importable before the package
            # finishes initializing); 'auto' stays the string — it
            # resolves against the first real batch at engine build
            from parallax_tpu.compile.bucketing import resolve_buckets
            resolved = resolve_buckets(self.shape_buckets, 1)
            if not isinstance(self.shape_buckets, str):
                self.shape_buckets = resolved
        if not self.bucket_mask_feed:
            raise ValueError("bucket_mask_feed must be a feed name")
        if self.tune_config is not None \
                and not isinstance(self.tune_config, TuneConfig):
            raise ValueError(
                f"tune_config must be a TuneConfig (or None), got "
                f"{type(self.tune_config).__name__} — a plain dict "
                f"here would silently skip the knob validation")

    # Reference-style setters (kept so ported driver code works unchanged).
    def set_sync(self, sync: bool) -> None:
        self.sync = sync

    def set_resource_info(self, resource_info) -> None:
        self.resource_info = resource_info

    def unused_knobs(self) -> list[str]:
        """Names of accepted-but-physically-unused knobs, for logging."""
        unused = []
        if self.sess_config is not None:
            unused.append("sess_config")
        ps = self.communication_config.ps_config
        if ps.protocol != "grpc":
            unused.append("communication_config.ps_config.protocol")
        if not ps.boundary_among_servers:
            unused.append(
                "communication_config.ps_config.boundary_among_servers")
        if not ps.boundary_between_workers_and_servers:
            unused.append("communication_config.ps_config."
                          "boundary_between_workers_and_servers")
        if self.communication_config.mpi_config.mpirun_options:
            unused.append("communication_config.mpi_config.mpirun_options")
        return unused


def normalize_run_option(run_option: str) -> str:
    opt = (run_option or consts.RUN_HYBRID).upper()
    opt = consts.LEGACY_RUN_ALIASES.get(opt, opt)
    if opt not in (consts.RUN_AR, consts.RUN_SHARD, consts.RUN_HYBRID):
        raise ValueError(
            f"unknown run_option {run_option!r}; expected one of "
            f"AR/SHARD/HYBRID (or legacy MPI/PS/HYBRID)")
    return opt


# Reference exports `Config` as an alias of ParallaxConfig
# (parallax/__init__.py:16-26).
Config = ParallaxConfig
