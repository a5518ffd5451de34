"""The hybrid parallelization engine.

This is the TPU-native replacement for the reference's entire graph-transform
layer (reference: common/graph_transform_lib.py + {ps,mpi,hybrid}/
graph_transform.py). Where the reference rewrites a serialized MetaGraphDef —
replicating subgraphs, inserting accumulators, token queues and Horovod ops —
we *choose a PartitionSpec per variable* and jit the user's unmodified
single-device step function over a device mesh; XLA emits the collectives.

Routing rule (reference: common/runner.py:93-119):
  * dense variable  -> replicated over the mesh; gradient all-reduced over
    ICI (was: Horovod/NCCL AllReduce).
  * sparse variable -> row-sharded over the 'shard' axis; rows exchanged via
    all_gather/psum_scatter in ops/embedding.py (was: gRPC parameter server
    with SparseConditionalAccumulator).
  * run_option AR    forces everything dense  (was: MPI mode).
  * run_option SHARD row-shards every variable whose leading dim divides the
    shard axis — ZeRO-style sharded storage with XLA-inserted all-gathers,
    the SPMD analogue of "all variables live on PS, workers hold mirrors"
    (was: PS mode with replicate_variables mirrors).
  * run_option HYBRID applies the per-variable rule; with no sparse
    variables it degenerates to pure AR, with no dense to pure SHARD,
    matching runner.py:93-111.

Sync semantics: SPMD collectives are inherently synchronous, so the
reference's accumulator/token-queue machinery (add_sync_op,
graph_transform_lib.py:330-582) has no equivalent here — the all-reduce IS
the barrier. `sync=False` (reference async PS,
ps/between_graph_parallel.py:137-146) is emulated as *bounded-staleness
delayed-gradient* training: the step applies the gradient computed one
step earlier, `params_{t+1} = params_t - opt(g(params_{t-1}))`, which
reproduces async PS's defining property (updates computed against stale
parameters, gradient compute overlapping newer updates) with a
deterministic staleness bound of 1 instead of the reference's unbounded
race; see SURVEY.md §7 hard-part 5.
"""

from __future__ import annotations

import dataclasses
import inspect
import threading
import time
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import struct
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from parallax_tpu.common import consts
from parallax_tpu.common.config import ParallaxConfig
from parallax_tpu.common.lib import parallax_log
from parallax_tpu.compile import bucketing, warmup as warmup_lib
from parallax_tpu.core import classify, mesh as mesh_lib, specs as specs_lib
from parallax_tpu.obs import _state as obs_state, \
    metrics as obs_metrics, numwatch, trace, xprof
from parallax_tpu.ops import embedding, sparse_optim


class Model:
    """A single-device model description — the unit the user hands to
    `parallel_run`, replacing the reference's single-GPU tf.Graph.

    * ``init_fn(rng) -> params`` — parameter pytree initializer. For a
      *stateful* model (``stateful=True``, e.g. BatchNorm statistics) it
      returns ``(params, model_state)``; only ``params`` gets gradients.
    * ``loss_fn(params, batch[, rng]) -> loss | (loss, metrics_dict)`` —
      pure forward+loss on one logical batch. Stateful models take
      ``loss_fn(params, model_state, batch, rng)`` and return
      ``(loss, metrics, new_model_state)`` — the SPMD analogue of TF's
      UPDATE_OPS: statistics reduce over the *global* batch because the
      whole step is one jitted program over the mesh.
    * ``optimizer`` — an optax GradientTransformation (default: sgd(0.01)).
    * ``sparse_params`` / ``dense_params`` — path-string overrides for the
      automatic classifier (classify.py).
    * ``gauges`` — which of ``loss_fn``'s scalar metrics the session
      shows as polled gauges, and under which names.
    """

    def __init__(self, init_fn: Callable, loss_fn: Callable,
                 optimizer: Optional[optax.GradientTransformation] = None,
                 sparse_params: Sequence[str] = (),
                 dense_params: Sequence[str] = (),
                 stateful: bool = False,
                 batch_specs: Optional[Dict[str, Any]] = None,
                 param_specs: Optional[Dict[str, Any]] = None,
                 slice_updaters: Optional[Dict[str, Any]] = None,
                 value_and_grad_fn: Optional[Callable] = None,
                 pipeline_info: Optional[Dict[str, Any]] = None,
                 gauges: Optional[Dict[str, Any]] = None):
        self.init_fn = init_fn
        self.loss_fn = loss_fn
        # gauge name -> the scalar entry of ``loss_fn``'s metrics it
        # shows, or ``(entry, "max")`` for the largest value any step
        # gave (a rare event must not hide between two polls).
        # ``session.metrics_snapshot()`` refreshes them from the last
        # dispatched step's outputs; nothing is read in the step loop.
        self.gauges = {
            name: (spec, "last") if isinstance(spec, str) else tuple(spec)
            for name, spec in (gauges or {}).items()}
        for name, (_, mode) in self.gauges.items():
            if mode not in ("last", "max"):
                raise ValueError(f"gauge {name!r}: unknown mode {mode!r}")
        # Pipeline capability record (ISSUE 18): a model that can run
        # its layer stack through ops/pipeline declares the schedule
        # here ({"schedule", "microbatches", "virtual_stages",
        # "pinned_stages", "num_layers", "model_dim", "act_itemsize",
        # optional "layer_costs"}). The tuner reads it via
        # costmodel.inputs_from_engine to admit and price pp>1 plans;
        # None (default) keeps the search strictly 2-D for this model.
        self.pipeline_info = (dict(pipeline_info)
                              if pipeline_info else None)
        # Optional fused loss+gradient override:
        # ``value_and_grad_fn(params, batch, rng) ->
        # (loss, metrics, grads)``. For models whose backward schedule
        # is part of the algorithm (1F1B pipelining,
        # ops/pipeline.pipeline_value_and_grad) and can't be expressed
        # as jax.value_and_grad(loss_fn). loss_fn must still exist
        # (classification/eval use it); stateless + sync only.
        self.value_and_grad_fn = value_and_grad_fn
        if value_and_grad_fn is not None and stateful:
            raise ValueError(
                "value_and_grad_fn is stateless-model only")
        # (sync-only is enforced by the engine at build time, where the
        # config is known)
        self.optimizer = optimizer or optax.sgd(0.01)
        self.sparse_params = tuple(sparse_params)
        self.dense_params = tuple(dense_params)
        self.stateful = stateful
        # path pattern (fnmatch) -> SliceUpdater (ops/sparse_optim.py):
        # under Config(sparse_grad_mode="slices"), these tables' grads
        # are captured as (ids, row) slices at their lookup sites and
        # applied scatter-only, bypassing `optimizer` (which then sees —
        # and e.g. global-norm-clips — only the remaining params, the
        # reference's exact grouping, language_model_graph.py:48-58).
        # A table registered here must be touched ONLY through
        # embedding_lookup; any other use would silently lose gradient.
        self.slice_updaters = dict(slice_updaters or {})
        # feed name -> PartitionSpec override (e.g. sequence-parallel
        # inputs sharded P('repl', 'shard') on [batch, seq])
        self.batch_specs = dict(batch_specs or {})
        # param path pattern (fnmatch) -> PartitionSpec override, for
        # layouts the dense/sparse classifier can't infer (e.g. expert
        # weights sharded P('shard', None, None), tensor-parallel kernels)
        self.param_specs = dict(param_specs or {})
        # feed name -> fn(np_array, mesh) applied host-side before
        # placement (e.g. zig-zag sequence permutation for balanced
        # causal ring attention)
        self.feed_transforms: Dict[str, Callable] = {}
        try:
            n_pos = len([
                p for p in inspect.signature(loss_fn).parameters.values()
                if p.kind in (p.POSITIONAL_ONLY, p.POSITIONAL_OR_KEYWORD)])
        except (TypeError, ValueError):
            n_pos = 4 if stateful else 2
        self._loss_takes_rng = n_pos >= (4 if stateful else 3)
        # the ``engine.model_traces`` counter of the registry whose
        # engine holds this model (``Engine.__init__`` hangs it here)
        self.trace_counter: Optional[obs_metrics.Counter] = None

    def call_init(self, rng):
        """Returns (params, model_state); model_state is None for
        stateless models."""
        out = self.init_fn(rng)
        if self.stateful:
            return out
        return out, None

    def call_loss(self, params, batch, rng, model_state=None):
        """Returns (loss, metrics, new_model_state). Runs only under a
        trace (``make_jaxpr``, ``eval_shape``, ``jit``), and counts
        each one: a whole trace of the user's forward pass is seconds
        of start-up at a real model's size."""
        if self.trace_counter is not None:
            self.trace_counter.inc()
        if self.stateful:
            args = (params, model_state, batch)
        else:
            args = (params, batch)
        if self._loss_takes_rng:
            out = self.loss_fn(*args, rng)
        else:
            out = self.loss_fn(*args)
        if self.stateful:
            loss, metrics, new_state = out
            return loss, dict(metrics), new_state
        if isinstance(out, tuple):
            loss, metrics = out
        else:
            loss, metrics = out, {}
        return loss, dict(metrics), None


@struct.dataclass
class TrainState:
    step: jax.Array
    params: Any
    opt_state: Any
    rng: jax.Array
    model_state: Any = None  # non-trainable state (e.g. BatchNorm stats)
    # sync=False only: the previous step's gradients, applied this step
    # (bounded-staleness emulation of the reference's async PS)
    pending_grads: Any = None
    # sparse_grad_mode="slices" only: {param path: updater state}
    # (e.g. adagrad row accumulators), updated scatter-only
    slice_state: Any = None


@dataclasses.dataclass
class ShardingPlan:
    """Resolved placement: one PartitionSpec per parameter leaf."""

    mesh: Mesh
    var_specs: Dict[str, specs_lib.VariableSpec]   # path -> classification
    param_pspecs: Any                              # pytree of PartitionSpec
    sharded_shapes: Tuple[Tuple[int, ...], ...]    # shapes routed to the
                                                   # collective lookup path

    def describe(self) -> str:
        return specs_lib.summarize(self.var_specs)


def build_plan(model: Model, mesh: Mesh, config: ParallaxConfig,
               params_shapes, example_batch,
               model_state_shapes=None) -> ShardingPlan:
    """Classify variables and choose PartitionSpecs (the 'graph transform')."""
    p = mesh_lib.num_shards(mesh)

    def abstract_loss(params, batch, rng, mstate):
        return model.call_loss(params, batch, rng, mstate)[0]

    rng_shape = jax.ShapeDtypeStruct((2,), jnp.uint32)
    with trace.span("engine.classify"):
        var_specs = classify.classify_params(
            abstract_loss, params_shapes, example_batch, rng_shape,
            model_state_shapes,
            sparse_override=model.sparse_params,
            dense_override=model.dense_params)

    flat, treedef = jax.tree_util.tree_flatten_with_path(params_shapes)
    paths = [classify._pathname(kp) for kp, _ in flat]

    replicate_dense = \
        config.communication_config.ps_config.replicate_variables

    def choose(path, leaf) -> P:
        shape = tuple(leaf.shape)
        vs = var_specs[path]
        shardable = len(shape) >= 1 and shape[0] % p == 0 and p > 1
        if config.run_option == consts.RUN_AR:
            return mesh_lib.replicated_spec()
        if config.run_option == consts.RUN_SHARD:
            return (mesh_lib.row_sharded_spec(len(shape)) if shardable
                    else mesh_lib.replicated_spec())
        # HYBRID
        if vs.is_sparse and shardable:
            return mesh_lib.row_sharded_spec(len(shape))
        if vs.is_sparse and not shardable:
            parallax_log.warning(
                "sparse variable %s has leading dim %s not divisible by "
                "shard axis %d; replicating (pad with "
                "ops.embedding.pad_vocab to shard it)", path,
                shape[:1], p)
        if not vs.is_sparse and not replicate_dense and shardable:
            # PSConfig.replicate_variables=False: dense variables stay
            # fully sharded (ZeRO-style) instead of mirrored — the SPMD
            # analogue of the reference running PS variables without
            # per-GPU mirror copies (graph_transform_lib.py:584-704).
            return mesh_lib.row_sharded_spec(len(shape))
        return mesh_lib.replicated_spec()

    import fnmatch

    def with_override(path, leaf, spec):
        for pattern, override in model.param_specs.items():
            if fnmatch.fnmatch(path, pattern):
                # 'pipe' resolves to 'shard' on meshes without a pipe
                # axis (core/mesh.resolve_spec): a model declares
                # stage-sharded variables ONCE and runs on both the
                # legacy 2-axis mesh and a (dp, tp, pp) mesh
                override = mesh_lib.resolve_spec(override, mesh)
                bad = spec_shape_mismatch(override, leaf.shape, mesh)
                if bad is not None:
                    dim, axes, size = bad
                    parallax_log.warning(
                        "param_specs override for %s: dim %d (%d) "
                        "not divisible by %s (%d); replicating",
                        path, dim, leaf.shape[dim], axes, size)
                    return spec
                return override
        return spec

    pspecs_flat = [with_override(path, leaf, choose(path, leaf))
                   for path, (_, leaf) in zip(paths, flat)]
    param_pspecs = jax.tree_util.tree_unflatten(treedef, pspecs_flat)

    # Only variables the plan actually row-sharded route through the
    # collective lookup (so e.g. RUN_AR never pays collective costs).
    # Routing is keyed on table shape inside the trace; warn when a dense
    # variable shares a shape with a sharded one (it would be misrouted —
    # numerically fine under shard_map but paying collectives it needn't).
    sharded_shapes = tuple(
        tuple(leaf.shape)
        for path, ((_, leaf), spec) in zip(paths, zip(flat, pspecs_flat))
        if var_specs[path].is_sparse
        and spec == mesh_lib.row_sharded_spec(len(leaf.shape)))
    for path, ((_, leaf), spec) in zip(paths, zip(flat, pspecs_flat)):
        if (tuple(leaf.shape) in sharded_shapes
                and not var_specs[path].is_sparse):
            parallax_log.warning(
                "dense variable %s shares shape %s with a row-sharded "
                "sparse variable; its lookups (if any) would take the "
                "collective path — pass Model(dense_params=...) shapes "
                "apart or use embedding_lookup(sharded=False)", path,
                tuple(leaf.shape))
    plan = ShardingPlan(mesh, var_specs, param_pspecs, sharded_shapes)
    parallax_log.info("sharding plan: %s (run_option=%s, shard axis=%d)",
                      plan.describe(), config.run_option, p)
    return plan


_pipeline_cache_guarded = False


def _guard_persistent_cache_for_pipeline():
    """Deserializing a persistently-cached pipeline-schedule executable
    (ops/pipeline ppermute schedules, custom value_and_grad) segfaults
    this XLA:CPU toolchain — a hard process kill, not an exception the
    caller could catch. The first pipeline engine built in a process
    therefore switches the persistent compilation cache off, BEFORE
    its first cache lookup: stale on-disk entries become unreachable
    as well as unwritable, and every executable compiled earlier in
    the process keeps its cached copy."""
    global _pipeline_cache_guarded
    if _pipeline_cache_guarded:
        return
    _pipeline_cache_guarded = True
    try:
        if jax.config.jax_compilation_cache_dir:
            jax.config.update("jax_compilation_cache_dir", None)
            parallax_log.warning(
                "pipeline engine: persistent XLA compilation cache "
                "disabled for this process — cached pipeline-schedule "
                "executables crash on reload with this toolchain")
    except Exception:
        pass


class Engine:
    """Builds and owns the compiled init/step executables for one mesh."""

    def __init__(self, model: Model, mesh: Mesh, config: ParallaxConfig,
                 example_batch,
                 metrics: Optional[obs_metrics.MetricsRegistry] = None):
        self.model = model
        self.mesh = mesh
        self.config = config
        if (model.pipeline_info is not None
                or model.value_and_grad_fn is not None):
            _guard_persistent_cache_for_pipeline()
        # observability (obs/): the owning session passes its registry;
        # direct Engine construction (tools/, tests) gets a private one
        self.metrics = metrics if metrics is not None \
            else obs_metrics.MetricsRegistry()
        self._recompiles = self.metrics.counter("engine.recompiles")
        model.trace_counter = self.metrics.counter("engine.model_traces")
        # batch-shape signatures already traced: a growing set means
        # shape-driven retraces (each one a full XLA compile)
        self._traced_signatures: set = set()
        # -- compile-ahead engine (compile/) -----------------------------
        # AOT-compiled step executables keyed by batch signature
        # (warmup()); step() dispatches to these before falling back to
        # the jit cache
        self._executables: Dict[Tuple, Any] = {}
        # the one that ran the last step, and its layer index once
        # somebody asked (layer_index())
        self._last_executable = None
        self._layer_index: Optional[Tuple[Any, Dict[str, Any]]] = None
        self._exec_hits = self.metrics.counter(
            "engine.executable_cache.hits")
        self._exec_misses = self.metrics.counter(
            "engine.executable_cache.misses")
        self.warmup_seconds: Dict[int, float] = {}
        # per-thread H2D wall time of the LAST shard_batch on that
        # thread (obs/timeline.py): the dispatch thread pops its own
        # value after a step — a prefetch-thread placement (overlapped,
        # off the critical path) can never leak into a dispatch row
        self._h2d_tl = threading.local()
        # cached XLA cost_analysis of the compiled step (forensics MFU)
        self._step_costs: Optional[Dict[str, float]] = None
        # batch-shape buckets: pad ragged batches onto a declared
        # signature set (compile/bucketing.py) so retraces are bounded
        self._buckets = None
        if config.shape_buckets is not None:
            if not isinstance(example_batch, dict):
                raise ValueError(
                    "shape_buckets requires dict feeds (name -> array); "
                    "got a %s example batch" % type(example_batch).__name__)
            local_n = max(1, mesh_lib.num_devices(mesh)
                          // jax.process_count())
            lead = bucketing._leading_dim(example_batch)
            self._buckets = bucketing.resolve_buckets(
                config.shape_buckets, lead if lead else 1, local_n)
            example_batch, _ = bucketing.bucket_batch(
                example_batch, self._buckets, config.bucket_mask_feed)
        if not config.sync:
            parallax_log.info(
                "sync=False: running bounded-staleness delayed-gradient "
                "training (each step applies the gradients computed %d "
                "step(s) earlier) — the deterministic SPMD emulation of "
                "the reference's async PS mode.", int(config.staleness))
        elif int(config.staleness) > 1:
            raise ValueError(
                f"staleness={config.staleness} has no effect with "
                f"sync=True; pass sync=False to parallel_run for "
                f"bounded-staleness training")
        self._debug_nans_was = None
        if config.debug_nans:
            self._debug_nans_was = bool(jax.config.jax_debug_nans)
            jax.config.update("jax_debug_nans", True)
            parallax_log.info("debug_nans enabled: steps re-run "
                              "op-by-op on NaN and raise at the source")
        rng = jax.random.PRNGKey(0)
        with trace.span("engine.build",
                        run_option=config.run_option,
                        num_shards=mesh_lib.num_shards(mesh)):
            params_shapes, mstate_shapes = jax.eval_shape(model.call_init,
                                                          rng)
            batch_shapes = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(np.shape(x), _dtype_of(x)),
                example_batch)
            self._params_shapes = params_shapes
            self._mstate_shapes = mstate_shapes
            self._batch_shapes = batch_shapes
            self._example_batch_dim = (
                bucketing._leading_dim(example_batch)
                if isinstance(example_batch, dict) else None)
            if self._buckets and isinstance(batch_shapes, dict):
                # declared buckets are EXPECTED signatures: pre-register
                # them so a multi-bucket stream never counts into
                # engine.recompiles (each bucket still costs one
                # compile — warmup() pays it ahead of step 0). Post-
                # placement signatures carry global shapes, hence the
                # process scale.
                for sig in bucketing.bucket_signatures(
                        batch_shapes, self._example_batch_dim,
                        self._buckets,
                        process_scale=self._feed_process_scale):
                    self._traced_signatures.add(sig)
            self.plan = build_plan(model, mesh, config, params_shapes,
                                   batch_shapes, mstate_shapes)
            self._param_shardings = jax.tree.map(
                lambda spec: NamedSharding(mesh, spec),
                self.plan.param_pspecs,
                is_leaf=lambda x: isinstance(x, P))
            self.batch_sharding_fn = lambda leaf_ndim: NamedSharding(
                mesh, mesh_lib.batch_spec(leaf_ndim))
            self._build()
        self.metrics.counter("engine.builds").inc()

    # -- construction ------------------------------------------------------

    def _resolve_slice_updaters(self) -> Dict[str, Any]:
        """{exact param path: updater} for sparse_grad_mode='slices'."""
        import fnmatch
        if (self.config.sparse_grad_mode != "slices"
                or not self.model.slice_updaters):
            if self.config.sparse_grad_mode == "slices":
                parallax_log.warning(
                    "sparse_grad_mode='slices' but the model declares no "
                    "slice_updaters; falling back to dense cotangents")
            return {}
        resolved = {}
        hit = set()
        for path in self.plan.var_specs:
            for pattern, upd in self.model.slice_updaters.items():
                if fnmatch.fnmatch(path, pattern):
                    resolved[path] = upd
                    hit.add(pattern)
                    break
        unmatched = set(self.model.slice_updaters) - hit
        if unmatched:
            # a typo'd pattern would silently train the table DENSELY
            # (clipped, through the optax optimizer) — never degrade
            # gradient semantics quietly
            raise ValueError(
                f"slice_updaters patterns {sorted(unmatched)} match no "
                f"param path; available: {sorted(self.plan.var_specs)}")
        return resolved

    def _slice_leaf_map(self, params, resolved):
        """{id(traced leaf): path} for the registered tables — computed
        per trace (tracer identity is only meaningful within a trace)."""
        flat, _ = jax.tree_util.tree_flatten_with_path(params)
        out = {}
        for kp, leaf in flat:
            path = classify._pathname(kp)
            if path in resolved:
                out[id(leaf)] = path
        return out

    def _build(self):
        model, mesh, config = self.model, self.mesh, self.config
        param_shardings = self._param_shardings
        avg = config.average_sparse
        ps_cfg = config.communication_config.ps_config
        local_agg = ps_cfg.local_aggregation
        dedup_cap = ps_cfg.dedup_capacity
        xrepl_sparse = ps_cfg.cross_replica_sparse
        sharded_shapes = self.plan.sharded_shapes
        self._lookup_records: list = []
        lookup_records = self._lookup_records

        slice_resolved = self._resolve_slice_updaters()
        if slice_resolved and not config.sync:
            raise ValueError(
                "sparse_grad_mode='slices' requires sync=True (the "
                "delayed-gradient async emulation stashes dense "
                "grad pytrees)")
        if slice_resolved and model.value_and_grad_fn is not None:
            raise ValueError(
                "sparse_grad_mode='slices' cannot combine with "
                "Model.value_and_grad_fn (slice capture lives in the "
                "engine's own loss wrapper)")
        if model.value_and_grad_fn is not None and not config.sync:
            raise ValueError(
                "Model.value_and_grad_fn requires sync=True (the fused "
                "schedule owns its backward; delayed-gradient emulation "
                "is untested with it)")

        def discover_slice_events(batch_shapes, mstate_shapes):
            """Abstract pass recording each registered table's lookup
            events (delta shapes) for ONE batch-shape signature — no
            math runs. Called per train_step trace, so a retrace on a
            new batch shape (e.g. a final partial batch) rediscovers
            matching delta shapes instead of reusing stale ones."""
            holder = []

            def _discover(params, batch, rng, mstate):
                cap = embedding.SliceCapture(
                    self._slice_leaf_map(params, slice_resolved))
                holder.append(cap)
                with embedding.sharded_lookup_scope(
                        mesh, sharded_shapes, avg,
                        local_aggregation=local_agg,
                        dedup_capacity=dedup_cap,
                        cross_replica_sparse=xrepl_sparse,
                        slice_capture=cap):
                    loss, _, _ = model.call_loss(params, batch, rng,
                                                 mstate)
                return loss
            with trace.span("engine.discover_slices"):
                jax.eval_shape(_discover, self._params_shapes,
                               batch_shapes,
                               jax.ShapeDtypeStruct((2,), jnp.uint32),
                               mstate_shapes)
            events = holder[0].events
            missing = set(slice_resolved) - {p for p, _, _ in events}
            if missing:
                raise ValueError(
                    f"slice_updaters registered for {sorted(missing)} "
                    f"but no embedding_lookup of those tables was "
                    f"traced; their gradients would be silently lost")
            parallax_log.info(
                "sparse_grad_mode=slices: %d lookup events over %s",
                len(events), sorted(slice_resolved))
            return events

        self._slice_resolved = slice_resolved
        if slice_resolved:
            # validate eagerly on the example batch (raises at build
            # time, not on the first step)
            discover_slice_events(self._batch_shapes,
                                  self._mstate_shapes)

        if slice_resolved:
            # the model's optimizer sees only non-slice params (so e.g.
            # its global-norm clip covers exactly the dense group, the
            # reference's grouping); slice tables are updated
            # scatter-only below
            labels = {p: ("slices" if p in slice_resolved else "rest")
                      for p in self.plan.var_specs}

            def label_fn(params):
                flat, treedef = jax.tree_util.tree_flatten_with_path(
                    params)
                return jax.tree_util.tree_unflatten(
                    treedef,
                    [labels[classify._pathname(kp)] for kp, _ in flat])
            tx = optax.multi_transform(
                {"slices": optax.set_to_zero(), "rest": model.optimizer},
                param_labels=label_fn)
        else:
            tx = model.optimizer

        def init_state(seed: jax.Array) -> TrainState:
            rng = jax.random.PRNGKey(seed)
            params, mstate = model.call_init(rng)
            params = jax.lax.with_sharding_constraint(params,
                                                      param_shardings)
            opt_state = tx.init(params)
            k = int(config.staleness)
            if config.sync:
                pending = None
            elif k == 1:
                pending = jax.tree.map(jnp.zeros_like, params)
            else:
                # ring of k gradient buffers: slot t % k holds the
                # gradients computed at step t, applied at step t + k
                pending = jax.tree.map(
                    lambda p: jnp.zeros((k,) + p.shape, p.dtype), params)
            slice_state = None
            if slice_resolved:
                # accumulators/moments follow their table's sharding
                # (otherwise a [V, D] state leaf would replicate per
                # device on a pod); scalar leaves (step counters) pass
                slice_state = {
                    path: _constrain_like_table(
                        upd.init(_get_path(params, path)),
                        _get_path(params, path),
                        _get_path(param_shardings, path))
                    for path, upd in slice_resolved.items()}
            return TrainState(step=jnp.zeros((), jnp.int32), params=params,
                              opt_state=opt_state,
                              rng=jax.random.PRNGKey(seed + 1),
                              model_state=mstate, pending_grads=pending,
                              slice_state=slice_state)

        def train_step(state: TrainState, batch):
            step_rng = jax.random.fold_in(state.rng, state.step)

            slice_events = []
            if slice_resolved:
                # runs once per trace: shapes are static within it
                slice_events = discover_slice_events(
                    jax.tree.map(
                        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                        batch),
                    jax.tree.map(
                        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                        state.model_state))
            deltas0 = tuple(
                jnp.zeros(shape, dtype)
                for _path, shape, dtype in slice_events)

            def loss_wrap(params, deltas):
                # one trace = one step's lookups; retraces (new batch
                # shape) replace rather than accumulate
                lookup_records.clear()
                cap = None
                if slice_resolved:
                    cap = embedding.SliceCapture(
                        self._slice_leaf_map(params, slice_resolved),
                        deltas=deltas)
                with embedding.sharded_lookup_scope(
                        mesh, sharded_shapes, avg,
                        records=lookup_records,
                        local_aggregation=local_agg,
                        dedup_capacity=dedup_cap,
                        cross_replica_sparse=xrepl_sparse,
                        slice_capture=cap):
                    loss, metrics, new_mstate = model.call_loss(
                        params, batch, step_rng, state.model_state)
                ids_list = (tuple(ids for _p, ids in cap.captured)
                            if cap is not None else ())
                return loss, (metrics, new_mstate, ids_list)

            if model.value_and_grad_fn is not None:
                # model-supplied fused loss+grad (e.g. 1F1B pipelining:
                # the backward schedule is part of the algorithm); the
                # scope still installs so current_mesh()/sharded lookups
                # work inside
                lookup_records.clear()
                with embedding.sharded_lookup_scope(
                        mesh, sharded_shapes, avg,
                        records=lookup_records,
                        local_aggregation=local_agg,
                        dedup_capacity=dedup_cap,
                        cross_replica_sparse=xrepl_sparse):
                    loss, metrics, grads = model.value_and_grad_fn(
                        state.params, batch, step_rng)
                new_mstate, ids_list, gdeltas = None, (), ()
            else:
                (loss, (metrics, new_mstate, ids_list)), \
                    (grads, gdeltas) = jax.value_and_grad(
                        loss_wrap, argnums=(0, 1),
                        has_aux=True)(state.params, deltas0)
            k = int(config.staleness)
            if config.sync:
                apply_grads, pending = grads, None
            elif k == 1:
                # delayed-gradient: apply last step's grads (computed
                # against the stale params, like an async PS push that
                # lands one update late); stash this step's for the next
                apply_grads, pending = state.pending_grads, grads
            else:
                # staleness k: slot t % k was written at step t - k
                slot = jnp.mod(state.step, k)
                apply_grads = jax.tree.map(
                    lambda b: jax.lax.dynamic_index_in_dim(
                        b, slot, 0, keepdims=False), state.pending_grads)
                pending = jax.tree.map(
                    lambda b, g: jax.lax.dynamic_update_index_in_dim(
                        b, g, slot, axis=0), state.pending_grads, grads)
            # the two update layers carry their names into the compiled
            # step (obs/xprof.LAYER_SCOPES; layer_index() reads them)
            with jax.named_scope("dense_update"):
                updates, opt_state = tx.update(
                    apply_grads, state.opt_state, state.params)
                if slice_resolved:
                    # don't route slice tables through apply_updates:
                    # their masked update is zero, but table + 0 still
                    # costs a full [V, D] buffer write per step
                    params = jax.tree_util.tree_map_with_path(
                        lambda kp, p, u: (
                            p if classify._pathname(kp) in slice_resolved
                            else optax.apply_updates(p, u)),
                        state.params, updates)
                else:
                    params = optax.apply_updates(state.params, updates)
            slice_state = state.slice_state
            if slice_resolved:
                # scatter-only table updates from the captured slices
                # (ids, d_delta) — the IndexedSlices path; duplicate ids
                # combine inside the updater
                per_path: Dict[str, list] = {}
                for (path, _s, _d), ids, dd in zip(slice_events,
                                                   ids_list, gdeltas):
                    per_path.setdefault(path, []).append((ids, dd))
                slice_state = dict(slice_state)
                with jax.named_scope("table_update"):
                    for path, items in per_path.items():
                        upd = slice_resolved[path]
                        ids_cat = jnp.concatenate(
                            [i.reshape(-1) for i, _ in items])
                        drows_cat = jnp.concatenate(
                            [d.reshape(-1, d.shape[-1])
                             for _, d in items])
                        table = _get_path(params, path)
                        # the updater picks its executor by the table's
                        # placement, which a traced array does not show
                        with sparse_optim.table_update_scope(path, mesh):
                            new_table, new_acc = upd.update(
                                table, slice_state[path], ids_cat,
                                drows_cat, average=avg)
                        params = _set_path(params, path, new_table)
                        slice_state[path] = _constrain_like_table(
                            new_acc, table,
                            _get_path(param_shardings, path))
            params = jax.lax.with_sharding_constraint(params,
                                                      param_shardings)
            new_state = state.replace(step=state.step + 1, params=params,
                                      opt_state=opt_state,
                                      model_state=new_mstate,
                                      pending_grads=pending,
                                      slice_state=slice_state)
            outputs = {"loss": loss, "global_step": new_state.step}
            outputs.update(metrics)
            if config.monitor_health:
                taken = {"grad_norm", "loss_finite"} & set(metrics)
                if taken:
                    # overwriting would silently change what the fetch
                    # returns based on an unrelated config flag
                    raise ValueError(
                        f"monitor_health=True reserves the output names "
                        f"'grad_norm'/'loss_finite' but the model's "
                        f"metrics already define {sorted(taken)}; "
                        f"rename the model metric(s)")
                # in-graph health signals (obs/health.py): a few FLOPs
                # next to the backward pass. gdeltas covers the slice
                # tables' captured row grads, so the norm is global
                # across both gradient representations.
                outputs["grad_norm"] = optax.global_norm((grads, gdeltas))
                outputs["loss_finite"] = jnp.isfinite(loss)
            if config.numerics_interval > 0 and obs_state.enabled:
                # numerics observatory (obs/numwatch.py): per-layer
                # stats tree under an in-graph sampling cond. The key
                # is ALWAYS present when enabled — AOT executables need
                # a static output structure — and the killswitch gate
                # is build-time, so PARALLAX_OBS=0 means zero extra
                # step outputs (check_obs_overhead asserts this
                # structurally). The sample is forced on a non-finite
                # loss/grad step so the rollback forensics always see
                # the trip step's per-layer evidence. gdeltas (slice
                # rows, varying shapes) stay out of the per-prefix
                # stats — the dense grads of a sliced table are zeros
                # there, not a numerics signal.
                if "numerics" in metrics:
                    raise ValueError(
                        "numerics_interval > 0 reserves the output "
                        "name 'numerics' but the model's metrics "
                        "already define it; rename the model metric")
                outputs["numerics"] = numwatch.step_numerics(
                    state.params, params, grads,
                    step=state.step,
                    interval=config.numerics_interval,
                    force=~jnp.isfinite(loss)
                    | ~jnp.isfinite(optax.global_norm((grads, gdeltas))))
            return new_state, outputs

        self._init_jit = jax.jit(init_state)
        self._step_jit = jax.jit(train_step, donate_argnums=0)
        self._exported_graph = False

    # -- public ops --------------------------------------------------------

    def init_state(self, seed: int = 0) -> TrainState:
        seed = int(seed)
        if not -2**31 <= seed < 2**31:
            # the jitted initialiser takes 32 signed bits and raises
            # OverflowError past them; seeds inside them are unchanged
            seed %= 2**31
        with trace.span("engine.init_state"), self.mesh:
            return self._init_jit(seed)

    def step(self, state: TrainState, batch,
             preplaced: bool = False) -> Tuple[TrainState, Dict]:
        """One training step. ``preplaced=True`` means ``batch`` already
        went through ``shard_batch`` (the async pipeline places batches
        on a background thread; re-placing would block the dispatch
        thread on a host round trip and re-run feed_transforms)."""
        if not preplaced:
            batch = self.shard_batch(batch)
        # signature AFTER placement: both the run() path and the
        # preplaced run_iter path then see the same (global) array
        # shapes — the ones _step_jit actually caches on — so mixing
        # the two paths can't fake a retrace on multi-host
        sig = exe = None
        if self._executables:
            sig = bucketing.batch_signature(batch)
            exe = self._executables.get(sig)
        self._note_batch_signature(batch, sig)
        with trace.span("engine.step"), self.mesh:
            if exe is not None:
                try:
                    new_state, outputs = exe(state, batch)
                    self._exec_hits.inc()
                    self._last_executable = exe
                except (TypeError, ValueError) as e:
                    # input rejection (shape/dtype/pytree/sharding
                    # drift, e.g. a shape-changing feed_transform) —
                    # raised BEFORE dispatch, so ``state`` is untouched:
                    # drop the executable and take the jit path, which
                    # compiles for whatever the inputs really are. A
                    # runtime failure (OOM, debug_nans) propagates
                    # instead: the state was donated, and retrying on
                    # deleted buffers would only mask the real error.
                    del self._executables[sig]
                    parallax_log.warning(
                        "AOT executable rejected its inputs (%s); "
                        "falling back to the jit path for signature %s",
                        e, sig)
                    new_state, outputs = self._step_jit(state, batch)
            else:
                if self._executables:
                    self._exec_misses.inc()
                new_state, outputs = self._step_jit(state, batch)
        if not self._exported_graph and self.config.export_graph_path:
            self._export_graph(state, batch)
        return new_state, outputs

    def warmup(self, state: TrainState,
               batch_sizes: Optional[Sequence[int]] = None
               ) -> Dict[int, float]:
        """AOT-compile the step executable for every declared batch
        bucket (``Config.shape_buckets``) — or for explicit
        ``batch_sizes`` — ahead of step 0, so no step in a bucketed
        stream ever stalls on an XLA compile. Lowers against ``state``'s
        real shardings; idempotent (already-compiled sizes are
        skipped). Returns {batch_size: compile_seconds}; also recorded
        in ``warmup_seconds`` and the ``engine.compile_seconds``
        histogram."""
        return warmup_lib.aot_warmup(self, state, batch_sizes)

    def _feed_sharding(self, name: str, ndim: int) -> NamedSharding:
        """The placement ``shard_batch`` will give feed ``name`` — the
        sharding warmup avals must carry for the AOT executable to
        accept real placed batches."""
        spec = self.model.batch_specs.get(name)
        if spec is not None:
            spec = mesh_lib.resolve_spec(spec, self.mesh)
            return NamedSharding(self.mesh, spec)
        return self.batch_sharding_fn(ndim)

    def _feed_process_scale(self, name: str) -> int:
        """local-to-global dim-0 factor for feed ``name``: how many
        processes its dim-0 placement spans. Default batch sharding
        spans every process; a ``batch_specs`` override only scales by
        the process span of its dim-0 mesh axes (a replicated or
        intra-process axis spans 1)."""
        if jax.process_count() == 1:
            return 1
        spec = self.model.batch_specs.get(name)
        if spec is None:
            return jax.process_count()
        spec = mesh_lib.resolve_spec(spec, self.mesh)
        if len(spec) == 0 or spec[0] is None:
            return 1
        axes = ((spec[0],) if isinstance(spec[0], str)
                else tuple(spec[0]))
        return int(np.prod([_process_span(self.mesh, a)
                            for a in axes]))

    def _bucket_avals(self, b: int) -> Dict[str, Any]:
        """Abstract batch (ShapeDtypeStructs with shardings) for bucket
        size ``b``: the example batch's shape tree with every
        batch-leading dim re-sized. Dims are global (multi-host
        placement scales the local feed by the process count); assumes
        shape-preserving feed_transforms — a transform that re-shapes
        makes the executable an unused cache entry (a per-step miss),
        never a wrong result."""
        if not isinstance(self._batch_shapes, dict):
            raise ValueError("warmup requires dict feeds (name -> array)")
        out = {}
        for name, leaf in self._batch_shapes.items():
            shape = bucketing.bucket_shape(
                tuple(leaf.shape), self._example_batch_dim, b,
                self._feed_process_scale(name))
            out[name] = jax.ShapeDtypeStruct(
                shape, leaf.dtype,
                sharding=self._feed_sharding(name, len(shape)))
        return out

    def _note_batch_signature(self, batch, sig=None) -> None:
        """Flag silent shape-driven retraces: every batch shape/dtype
        signature beyond the first costs a full XLA recompile of the
        step — a loop feeding ragged final batches is compile-bound
        while looking healthy. Counted as ``engine.recompiles`` and
        warned once per new signature. Declared ``shape_buckets``
        signatures are pre-registered as expected and never count.
        ``sig``: the signature when the step dispatch already computed
        it (compile/bucketing.batch_signature — the same sorted
        fast-path as below)."""
        if not obs_state.enabled:
            return
        if sig is None:
            # ONE signature function for noting, dispatch and
            # pre-registration: a second implementation here could
            # key the same batch two ways and fake a retrace
            sig = bucketing.batch_signature(batch)
        if sig in self._traced_signatures:
            return
        first = not self._traced_signatures
        self._traced_signatures.add(sig)
        if not first:
            self._recompiles.inc()
            parallax_log.warning(
                "new batch shape signature #%d triggers an XLA retrace "
                "of the step (signature: %s); declare "
                "Config.shape_buckets=[...] (or 'auto') so ragged "
                "batches are padded onto a fixed set of compiled "
                "bucket shapes — see docs/parallax_api.md "
                "'Compilation, warmup & caching'",
                len(self._traced_signatures) - 1,
                [(n, s) for n, s, _ in sig])

    def _step_executable(self):
        exe = self._last_executable
        if exe is None and self._executables:
            exe = next(iter(self._executables.values()))
        return exe

    def executable_text(self) -> Optional[str]:
        """The optimized HLO text of the AOT executable ``layer_index()``
        reads (shapes and all), or None where there is none. Nothing is
        lowered or compiled for a read."""
        exe = self._step_executable()
        return exe.as_text() if exe is not None else None

    def layer_index(self) -> Optional[Dict[str, Any]]:
        """Which layer each instruction of the compiled step belongs
        to: ``{"module": the program's name as a device trace prints
        it, "layers": {instruction name: one of xprof.LAYER_SCOPES or
        None}, "scopes_found": the layers that own an instruction,
        "hlo_index": xprof.build_hlo_index of the text (what
        ``xprof.attribute`` joins on)}``, read off the AOT executable
        that ran the last step (before any step: the first one
        ``warmup()`` compiled). None where there is no AOT executable:
        nothing is lowered or compiled for a read. Built on the first
        call for an executable and kept; never on the step path, and it
        still answers after ``close()``."""
        exe = self._step_executable()
        if exe is None:
            return None
        if self._layer_index is None or self._layer_index[0] is not exe:
            text = exe.as_text()
            hlo_index = xprof.build_hlo_index(text)
            layers = {name: xprof.layer_of(meta)
                      for name, meta in hlo_index.items()}
            found = set(layers.values())
            self._layer_index = (exe, {
                "module": xprof.module_name(text),
                "layers": layers,
                "scopes_found": [s for s in xprof.LAYER_SCOPES
                                 if s in found],
                "hlo_index": hlo_index})
        return self._layer_index[1]

    def close(self):
        """Restore process-global settings this engine changed
        (jax_debug_nans is process-wide; don't leak it into later
        sessions). Drops nothing: the executables, and with them
        ``layer_index()``, outlive it."""
        if self._debug_nans_was is not None:
            jax.config.update("jax_debug_nans", self._debug_nans_was)
            self._debug_nans_was = None

    def shard_batch(self, batch):
        """Place a host batch onto the mesh, sharded on dim 0 by default
        (the reference's per-replica feed splitting,
        session_context.py:205-233); Model.batch_specs overrides the
        layout per feed name (e.g. sequence-parallel inputs). With
        ``Config.shape_buckets`` declared, ragged batches are first
        padded up to their bucket with the mask feed zeroed over the
        tail (compile/bucketing.py) — full batches pass through
        bit-identical — so every caller (run / run_iter / place_batch /
        prefetch_to_device) presents a bounded signature set."""
        t0 = time.perf_counter()
        try:
            with trace.span("engine.h2d_place"):
                if self._buckets is not None and isinstance(batch, dict):
                    batch, _ = bucketing.bucket_batch(
                        batch, self._buckets,
                        self.config.bucket_mask_feed)
                return self._shard_batch_impl(batch)
        finally:
            self._h2d_tl.seconds = time.perf_counter() - t0

    def _shard_batch_impl(self, batch):
        return place_host_batch(self.mesh, batch,
                                overrides=self.model.batch_specs,
                                transforms=self.model.feed_transforms,
                                default_sharding_fn=self.batch_sharding_fn)

    def pop_h2d_seconds(self) -> float:
        """The calling thread's last ``shard_batch`` wall time, then 0
        until its next placement — the dispatch thread's per-step H2D
        share for the timeline (obs/timeline.py). Thread-local, so
        overlapped prefetch-thread placements never count."""
        s = getattr(self._h2d_tl, "seconds", 0.0)
        self._h2d_tl.seconds = 0.0
        return s

    def step_cost_analysis(self, cheap_only: bool = True
                           ) -> Dict[str, float]:
        """XLA ``cost_analysis`` of one compiled train step (notably
        ``flops`` — the numerator of the timeline's per-step MFU),
        cached after the first resolution; {} when unavailable.

        ``cheap_only=True`` (the monitoring path) only consults an
        already-AOT-compiled executable (``warmup()``); with False
        (flight dumps, explicit calls) the step is re-traced and
        lowered from its example avals — a one-time host-side cost,
        never a device execution."""
        if self._step_costs is not None:
            return self._step_costs
        costs: Dict[str, float] = {}
        try:
            if self._executables:
                # {} when the backend reports nothing
                costs = dict(next(iter(
                    self._executables.values())).cost_analysis() or {})
            elif not cheap_only:
                state_shapes = jax.eval_shape(
                    self._init_jit,
                    jax.ShapeDtypeStruct((), jnp.int32))
                lowered = self._step_jit.lower(state_shapes,
                                               self._batch_shapes)
                costs = dict(lowered.cost_analysis() or {})
            else:
                return {}
        except Exception as e:  # never fail training for forensics
            parallax_log.warning("step cost analysis failed: %s", e)
            # NOT cached: a transient failure must not permanently
            # block the documented cheap_only=False retry path
            return {}
        self._step_costs = costs
        return costs

    def sparse_wire_bytes_per_step(self) -> Dict[str, int]:
        """Bytes-on-wire per step for the sparse path vs the dense
        alternative (the BASELINE.json north-star metric). Exact for
        every configuration except a user-declared
        ``PSConfig.dedup_capacity`` below the exactness bound, where it
        is a LOWER bound: steps whose distinct-id count overflows the
        declared capacity ship the full uncompressed exchange at
        runtime (the guarded `lax.cond` fallback) while the record
        counts the declared capacity.

        Sparse path: one record per sharded lookup event in the latest
        trace (ops/embedding.py) — forward all_gather(ids, int32) +
        psum_scatter(rows), backward all_gather(row grads), O(ids · dim)
        each; with local_aggregation the recorded id count is the
        post-combine unique capacity, so the two-stage win shows up here
        directly. Each record also carries the mesh-total cross-replica
        combine bytes (dense [rows/shard, dim] psum over 'repl' or the
        sparse full-mesh gather's extra rows — whichever the static
        chooser picked; zero on single-repl meshes). Dense alternative:
        ring all-reduce of every row-sharded variable's full gradient
        (~2 bytes moved per gradient byte), counted per *variable* from
        the plan so same-shaped tables don't collapse. Call after the
        first step has compiled.
        """
        if not self._lookup_records and self.plan.sharded_shapes:
            # trace-dependent state (records are refilled per trace):
            # before the first step there is nothing to report, and
            # silently returning zeros would masquerade as "no wire
            # traffic" (VERDICT r3 weak item 6)
            raise RuntimeError(
                "sparse_wire_bytes_per_step() called before any step "
                "was traced; run at least one session step first")
        # per-record formulas live in tune/costmodel.py — ONE source of
        # truth shared with the analytic plan scorer and
        # tools/wire_bytes_report.py (ISSUE 10): row planes (fwd
        # psum_scatter + bwd all_gather) carry the TABLE's dtype — a
        # bf16 table halves them on the wire; id/count planes are
        # always int32
        from parallax_tpu.tune import costmodel as tune_costmodel
        sparse_bytes = 0
        per_lookup = []
        for tshape, n_ids, n_cnt, repl_bytes, sparse_repl, elem in \
                self._lookup_records:
            sparse_bytes += tune_costmodel.lookup_wire_bytes(
                tshape, n_ids, n_cnt, repl_bytes, elem)
            per_lookup.append({
                "table_shape": tshape,
                "ids_on_wire": n_ids,
                "counts_on_wire": n_cnt,
                "cross_replica_bytes": repl_bytes,
                "cross_replica_sparse": sparse_repl,
                "elem_bytes": elem,
            })
        dense_bytes = 0
        for vs in self.plan.var_specs.values():
            if vs.is_sparse and tuple(vs.shape) in \
                    self.plan.sharded_shapes:
                # the dense alternative ships the full [V, D] gradient in
                # the variable's own dtype (cotangent dtype == primal)
                e = (jnp.dtype(vs.dtype).itemsize
                     if vs.dtype is not None else 4)
                dense_bytes += tune_costmodel.dense_alternative_bytes(
                    vs.shape, e)
        return {"sparse_path_bytes": sparse_bytes,
                "dense_allreduce_bytes": dense_bytes,
                "per_lookup": per_lookup}

    def _export_graph(self, state, batch):
        """Dump compiled-step HLO text (reference: export_graph_path dumps
        the transformed MetaGraph, common/lib.py:258-264)."""
        import os
        self._exported_graph = True
        try:
            # lower() on the already-jitted callable reuses its traced
            # computation (no duplicate trace, no private attributes)
            lowered = self._step_jit.lower(state, batch)
            path = self.config.export_graph_path
            os.makedirs(path, exist_ok=True)
            with open(os.path.join(path, "train_step.stablehlo.txt"),
                      "w") as f:
                f.write(lowered.as_text())
            parallax_log.info("exported compiled graph to %s", path)
        except Exception as e:  # non-fatal observability feature
            parallax_log.warning("graph export failed: %s", e)


def place_host_batch(mesh: Mesh, batch,
                     overrides: Optional[Dict[str, Any]] = None,
                     transforms: Optional[Dict[str, Callable]] = None,
                     default_sharding_fn: Optional[Callable] = None):
    """Place a host feed pytree onto ``mesh`` — the one placement rule
    shared by the training engine (``Engine.shard_batch``) and the
    serving layer (serve/session.py): per-feed spec overrides, host-side
    feed transforms, multi-host process-local assembly, and a single
    batched ``device_put`` for the whole dict (one runtime dispatch
    instead of one host->device round trip per feed).

    ``default_sharding_fn(ndim) -> NamedSharding`` decides placement
    for feeds without an override (the engine shards dim 0 over the
    whole mesh; the serving layer replicates when a micro-batch bucket
    doesn't divide the local devices)."""
    overrides = overrides or {}
    transforms = transforms or {}
    n = mesh_lib.num_devices(mesh)
    if default_sharding_fn is None:
        default_sharding_fn = lambda ndim: NamedSharding(  # noqa: E731
            mesh, mesh_lib.batch_spec(ndim))
    multiprocess = jax.process_count() > 1

    def resolve(name, x):
        """-> (host array, target sharding) for one feed leaf."""
        x = np.asarray(x)
        if name in transforms:
            x = np.asarray(transforms[name](x, mesh))
        if name in overrides:
            spec = mesh_lib.resolve_spec(overrides[name], mesh)
            # in multiprocess mode the caller feeds a process-local
            # slice, so each dim's requirement shrinks by the process
            # span of its axes
            bad = spec_shape_mismatch(spec, x.shape, mesh,
                                      local=multiprocess)
            if bad is not None:
                dim, axes, need = bad
                raise ValueError(
                    f"feed {name!r} dim {dim} of size "
                    f"{x.shape[dim]} is not divisible by the "
                    f"{need}-way (local) mesh axes {axes} in its "
                    f"PartitionSpec; pad that dimension")
            return x, NamedSharding(mesh, spec)
        sharding = default_sharding_fn(x.ndim)
        if sharding.spec and sharding.spec[0] is not None:
            local_n = max(1, n // jax.process_count())
            if x.ndim >= 1 and x.shape[0] % local_n != 0:
                raise ValueError(
                    f"batch dimension {x.shape[0]} is not divisible by "
                    f"the {local_n} local devices of the mesh; pad the "
                    f"batch (or feed per-replica lists of equal size)")
        return x, sharding

    if isinstance(batch, dict):
        resolved = {k: jax.tree.map(lambda x, k=k: resolve(k, x), v)
                    for k, v in batch.items()}
    else:
        resolved = jax.tree.map(lambda x: resolve("", x), batch)
    pairs_leaf = lambda v: (isinstance(v, tuple) and len(v) == 2
                            and isinstance(v[1], NamedSharding))
    if multiprocess:
        # each host feeds its local slice of the global batch
        # (reference: each worker's shard, shard.py semantics)
        return jax.tree.map(
            lambda v: jax.make_array_from_process_local_data(v[1],
                                                             v[0]),
            resolved, is_leaf=pairs_leaf)
    flat, treedef = jax.tree_util.tree_flatten(resolved,
                                               is_leaf=pairs_leaf)
    placed = jax.device_put([x for x, _ in flat],
                            [s for _, s in flat])
    return jax.tree_util.tree_unflatten(treedef, placed)


def _process_span(mesh: Mesh, axis: str) -> int:
    """How many distinct processes the devices along ``axis`` belong to
    (other axes fixed at index 0). 1 means the axis is intra-process."""
    names = list(mesh.axis_names)
    idx = [0] * len(names)
    procs = set()
    ax = names.index(axis)
    for i in range(mesh.shape[axis]):
        idx[ax] = i
        procs.add(mesh.devices[tuple(idx)].process_index)
    return max(1, len(procs))


def spec_shape_mismatch(spec, shape, mesh, local: bool = False):
    """Check a PartitionSpec against an array shape: every constrained dim
    must divide the product of its mesh axes. With ``local=True`` the
    shape is a process-local slice, so each dim's requirement shrinks by
    the number of processes its axes actually span (not by the global
    process count — intra-process axes still demand the full split).
    Returns (dim, axes, required) for the first violation, or None."""
    for dim, axes in enumerate(spec):
        if axes is None or dim >= len(shape):
            continue
        axes = (axes,) if isinstance(axes, str) else tuple(axes)
        size = int(np.prod([mesh.shape[a] for a in axes]))
        if local:
            span = int(np.prod([_process_span(mesh, a) for a in axes]))
            size = max(1, size // span)
        if shape[dim] % size != 0:
            return dim, axes, size
    return None


def _dtype_of(x):
    d = getattr(x, "dtype", None)
    if d is not None:
        return d
    return np.asarray(x).dtype


def _constrain_like_table(state, table, sharding):
    """Apply the table's sharding to every state leaf shaped like the
    table (adagrad accs, adam moments); leave other leaves (step
    counters) unconstrained."""
    return jax.tree.map(
        lambda x: (jax.lax.with_sharding_constraint(x, sharding)
                   if getattr(x, "shape", None) == table.shape else x),
        state)


def _get_path(tree, path: str):
    """Fetch a leaf by its classify-style 'a/b/0/c' path."""
    node = tree
    for part in path.split("/"):
        if isinstance(node, (list, tuple)):
            node = node[int(part)]
        else:
            node = node[part]
    return node


def _set_path(tree, path: str, value):
    """Functionally replace a leaf by path (dict/list/tuple pytrees)."""
    parts = path.split("/")

    def rec(node, i):
        if i == len(parts):
            return value
        p = parts[i]
        if isinstance(node, dict):
            new = dict(node)
            new[p] = rec(node[p], i + 1)
            return new
        if isinstance(node, (list, tuple)):
            idx = int(p)
            items = list(node)
            items[idx] = rec(items[idx], i + 1)
            return tuple(items) if isinstance(node, tuple) else items
        raise TypeError(
            f"cannot set path {path!r} inside node of type {type(node)}")

    return rec(tree, 0)
