"""ServeSession — put a model behind a request queue.

The training side (session.py / core/engine.py) optimizes steps/sec of
one long-lived loop; this is the other half of the ROADMAP north star:
many small independent requests, each with its own latency budget.
One object owns the whole serving stack:

* **planning** — the inference fn is jitted over the same
  ``('repl','shard')`` mesh the engine trains on; with a ``Model``
  given, parameter placement comes from the engine's own
  :func:`~parallax_tpu.core.engine.build_plan` (row-sharded embedding
  tables, replicated dense — the training layout carried into
  serving); otherwise parameters replicate (the standard serving
  layout). Batch placement reuses
  :func:`~parallax_tpu.core.engine.place_host_batch`.
* **a bounded signature set** — requests are padded onto declared
  length buckets (``ServeConfig.length_buckets``, per-request ragged
  feeds) and formed batches onto batch buckets
  (``ServeConfig.batch_buckets``, default powers of two up to
  ``max_batch``) — the ``compile/`` bucketing discipline applied to
  serving. Every (batch, length) signature is **AOT-compiled at
  construction** (``warmup=True``), so live traffic never meets an XLA
  compile; any dispatch that misses the executable table counts into
  ``serve.recompiles`` (a healthy session holds it at 0).
* **the dynamic micro-batcher** (serve/batcher.py) for one-shot
  inference, or **the slot-based continuous scheduler**
  (serve/continuous.py) when a :class:`DecodeProgram` is passed.
* **observability** — ``serve.*`` metrics (queue depth, batch
  occupancy, request latency, time-to-first-token, tokens/sec,
  shed/timeout counters) in the shared registry and a
  ``serve.request`` span per request on the obs/ timeline.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Callable, Dict, Optional, Sequence

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from parallax_tpu.common.config import ParallaxConfig
from parallax_tpu.common.lib import parallax_log
from parallax_tpu.compile import bucketing, cache as compile_cache
from parallax_tpu.core import engine as engine_lib, mesh as mesh_lib
from parallax_tpu.obs import _state as obs_state
from parallax_tpu.obs import metrics as obs_metrics, reqtrace, trace
from parallax_tpu.serve.batcher import (DeadlineExceeded, MicroBatcher,
                                        ReplicaUnavailable, Request,
                                        RequestQueue, ServeClosed,
                                        ServeError, ServeOverloaded)


class ServeSession:
    """Serve ``infer_fn(params, batch) -> outputs`` (one-shot mode) or
    a :class:`~parallax_tpu.serve.continuous.DecodeProgram` (continuous
    decode mode) behind a dynamic micro-batching request queue.

    One-shot mode::

        serve = ServeSession(infer_fn, params, example_feed={"x": x0},
                             config=parallax.Config(
                                 serve_config=ServeConfig(max_batch=8)))
        req = serve.submit({"x": x}, deadline_ms=50)
        y = req.result()
        serve.close()

    ``example_feed`` is ONE request's feed (no batch dim); outputs must
    carry the batch on dim 0 of every leaf (scalars pass through to
    every request unchanged). Decode mode replaces ``infer_fn`` with
    ``program=`` and ``submit`` returns the decoded token array.
    """

    def __init__(self, infer_fn: Optional[Callable] = None,
                 params: Any = None, *,
                 example_feed: Optional[Dict[str, Any]] = None,
                 config: Optional[ParallaxConfig] = None,
                 model: Optional[engine_lib.Model] = None,
                 mesh=None, num_partitions: Optional[int] = None,
                 ragged_feeds: Sequence[str] = (),
                 pad_value=0, warmup: bool = True,
                 program=None,
                 metrics: Optional[obs_metrics.MetricsRegistry] = None,
                 flight=None, replica_id=None, faults=None,
                 on_fatal=None, on_error=None,
                 check_outputs: bool = False):
        if jax.process_count() > 1:
            raise ValueError(
                "ServeSession is single-process (each serving replica "
                "owns its own queue); run one session per host")
        if (infer_fn is None) == (program is None):
            raise ValueError(
                "pass exactly one of infer_fn (one-shot) or program "
                "(continuous decode)")
        self._config = config or ParallaxConfig()
        sc = self._config.serve_config
        compile_cache.ensure_persistent_cache(
            self._config.compilation_cache_dir)
        self.mesh = mesh if mesh is not None else mesh_lib.build_mesh(
            num_partitions=num_partitions)
        self.metrics = metrics if metrics is not None \
            else obs_metrics.MetricsRegistry()
        self._recompiles = self.metrics.counter("serve.recompiles")
        self._requests = self.metrics.counter("serve.requests")
        self._completed = self.metrics.counter("serve.completed")
        self._batches = self.metrics.counter("serve.batches")
        self._latency = self.metrics.histogram("serve.request_latency_ms")
        self._occupancy = self.metrics.histogram("serve.batch_occupancy")
        self._step_ms = self.metrics.histogram("serve.step_ms")
        self._batcher_ms = self.metrics.histogram(
            "serve.batcher_overhead_ms")
        self._h2d_ms = self.metrics.histogram("serve.h2d_ms")
        # flight recorder (obs/flightrec.py): a deadline/SLO breach is
        # an incident worth a post-mortem — the training session's
        # serve() handoff passes its recorder so the dump carries the
        # shared registry's serve.* metrics next to the training state;
        # a standalone ServeSession may pass its own (or None)
        self._flight = flight
        # fleet wiring (ISSUE 7): replica identity, deterministic
        # fault-injection hooks (serve/faults.py), death/error
        # reporting, and the non-finite output guard the fleet router's
        # error-rate probe rides on
        self.replica_id = replica_id
        self._faults = faults
        self._check_outputs = bool(check_outputs)
        # request forensics (ISSUE 12): the per-request lifecycle ring
        # behind the serve.timeline.* / serve.slo.* gauges. Standalone
        # sessions own their records; fleet sub-requests carry the
        # FLEET's record through submit(rec=...) so a failed-over
        # request keeps ONE decomposition across hops (and lands in
        # the fleet's ring, not this one).
        self.reqtrace = reqtrace.RequestTraceRing(self.metrics)
        self._queue = RequestQueue(
            sc.max_queue, self.metrics,
            on_timeout=self._on_deadline_breach,
            tenant_quotas=getattr(sc, "tenant_quotas", None),
            default_tenant_quota=getattr(sc, "default_tenant_quota",
                                         None))
        self._closed = False
        self._close_lock = threading.Lock()

        if program is not None:
            # continuous decode: the scheduler owns dispatch
            from parallax_tpu.serve.continuous import ContinuousScheduler
            self._params = self._place_params(params, model, program)
            self._scheduler = ContinuousScheduler(
                program, self._params, sc, self.metrics, self._queue,
                on_deadline_breach=self._on_deadline_breach,
                replica_id=replica_id, faults=faults,
                on_fatal=on_fatal, on_error=on_error,
                state_sharding=NamedSharding(self.mesh, P()))
            self._batcher = None
            return
        self._scheduler = None

        if params is None or example_feed is None:
            raise ValueError(
                "one-shot serving needs params and example_feed (one "
                "request's feed dict, no batch dim)")
        self._infer_fn = infer_fn
        self._example = {k: np.asarray(v) for k, v in example_feed.items()}
        self._ragged = tuple(ragged_feeds)
        self._pad_value = pad_value
        unknown = set(self._ragged) - set(self._example)
        if unknown:
            raise ValueError(
                f"ragged_feeds {sorted(unknown)} not in example_feed "
                f"{sorted(self._example)}")
        if self._ragged and not sc.length_buckets:
            raise ValueError(
                "ragged_feeds declared but ServeConfig.length_buckets "
                "is unset; declare the length signature set so live "
                "traffic cannot recompile")
        for name in self._ragged:
            if self._example[name].ndim < 1:
                raise ValueError(
                    f"ragged feed {name!r} must have a length axis "
                    f"(ndim >= 1)")
        self._batch_buckets = sc.resolved_batch_buckets()
        self._params = self._place_params(params, model, None)
        self._infer_jit = jax.jit(self._infer_fn)
        # the admitted per-request signatures: a submit whose padded
        # feed is not one of these is REFUSED at admission (it could
        # only be served by a serve-time compile)
        lengths = (sc.length_buckets if self._ragged else None) or (None,)
        self._admitted = {
            bucketing.batch_signature(self._padded_example(L))
            for L in lengths}
        # signature -> AOT executable; populated by warmup(), consulted
        # on every dispatch (a miss = a serve-time compile = counted)
        self._executables: Dict[tuple, Any] = {}
        self.warmup_seconds: Dict[tuple, float] = {}
        if warmup:
            self.warmup()
        self._batcher = MicroBatcher(self._queue, self._run_batch,
                                     sc.max_batch, sc.max_wait_ms,
                                     on_error=on_error,
                                     on_fatal=on_fatal)

    # -- planning ----------------------------------------------------------

    def _place_params(self, params, model, program):
        """Place the parameter pytree on the serve mesh: by the
        engine's sharding plan when a Model is given (the training
        layout — row-sharded tables stay sharded), else replicated
        (the standard serving layout)."""
        if params is None:
            raise ValueError("ServeSession needs a params pytree")
        leaves = jax.tree_util.tree_leaves(params)
        if model is None and leaves and all(
                isinstance(x, jax.Array)
                and getattr(getattr(x, "sharding", None), "mesh", None)
                == self.mesh for x in leaves):
            # the session.serve() handoff: the live TrainState's params
            # already sit on this mesh under the training plan — keep
            # that placement (no copy, row-sharded tables stay sharded)
            return params
        if model is not None:
            params_shapes = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(
                    np.shape(x), engine_lib._dtype_of(x)), params)
            example = self._plan_example_batch(program)
            batch_shapes = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(np.shape(x),
                                               np.asarray(x).dtype),
                example)
            plan = engine_lib.build_plan(model, self.mesh, self._config,
                                         params_shapes, batch_shapes)
            shardings = jax.tree.map(
                lambda spec: NamedSharding(self.mesh, spec),
                plan.param_pspecs,
                is_leaf=lambda x: isinstance(x, P))
            return jax.device_put(params, shardings)
        repl = NamedSharding(self.mesh, P())
        return jax.tree.map(lambda x: jax.device_put(x, repl), params)

    def _plan_example_batch(self, program):
        """A full-batch example feed for plan classification."""
        b = int(self._config.serve_config.max_batch)
        if program is not None:
            ex = program.example_feed()
        else:
            ex = self._padded_example(self._max_length_bucket())
        return {k: np.stack([v] * b) for k, v in ex.items()}

    # -- the bounded signature set ----------------------------------------

    def _max_length_bucket(self) -> Optional[int]:
        lb = self._config.serve_config.length_buckets
        return lb[-1] if lb else None

    def _padded_example(self, L: Optional[int]) -> Dict[str, np.ndarray]:
        """The example feed with every ragged feed padded to length
        ``L`` (identity when no length buckets are declared)."""
        if L is None or not self._ragged:
            return self._example
        out = dict(self._example)
        for name in self._ragged:
            out[name] = bucketing.pad_axis0(
                out[name][:L], L, self._pad_value)
        return out

    def _batch_sharding_fn(self, bucket: int):
        """Placement rule for a batch of size ``bucket``: sharded on
        dim 0 over the mesh when the bucket divides the devices (data-
        parallel serving), replicated otherwise (small micro-batches on
        big meshes). Decided per BUCKET, so placement is part of the
        signature and stable across dispatches."""
        n = mesh_lib.num_devices(self.mesh)
        if bucket % n == 0:
            return lambda ndim: NamedSharding(self.mesh,
                                              mesh_lib.batch_spec(ndim))
        return lambda ndim: NamedSharding(self.mesh, P())

    def _signature_set(self):
        """Every (batch bucket, length bucket) aval dict the session
        serves — the COMPLETE set warmup compiles."""
        lengths = (self._config.serve_config.length_buckets
                   if self._ragged else None) or (None,)
        for L in lengths:
            ex = self._padded_example(L)
            for b in self._batch_buckets:
                shard_fn = self._batch_sharding_fn(b)
                avals = {
                    name: jax.ShapeDtypeStruct(
                        (b,) + tuple(v.shape), v.dtype,
                        sharding=shard_fn(v.ndim + 1))
                    for name, v in ex.items()}
                yield (b, L), avals

    def warmup(self) -> Dict[tuple, float]:
        """AOT-compile every declared (batch, length) signature;
        idempotent. Returns {(batch, length): compile seconds}."""
        stats: Dict[tuple, float] = {}
        for key, avals in self._signature_set():
            sig = bucketing.batch_signature(avals)
            if sig in self._executables:
                continue
            t0 = time.perf_counter()
            with trace.span("serve.warmup_compile", batch=key[0],
                            length=key[1]):
                self._executables[sig] = self._infer_jit.lower(
                    self._params, avals).compile()
            dt = time.perf_counter() - t0
            self.metrics.histogram("serve.compile_seconds").record(dt)
            stats[key] = dt
            parallax_log.info(
                "serve warmup: compiled signature batch=%s length=%s "
                "in %.2fs", key[0], key[1], dt)
        self.warmup_seconds.update(stats)
        return stats

    # -- admission ---------------------------------------------------------

    def submit(self, feed: Dict[str, Any],
               deadline_ms: Optional[float] = None,
               max_new_tokens: Optional[int] = None,
               rec: Optional[reqtrace.RequestRecord] = None,
               tenant: Any = None,
               slo_class: Optional[str] = None) -> Request:
        """Admit one request; returns its :class:`Request` future.

        Raises :class:`ServeOverloaded` when admission control sheds it
        (queue full), :class:`TenantQuotaExceeded` when ``tenant`` is
        at its admission quota, and :class:`ServeClosed` after
        ``close()``. The deadline (``deadline_ms``, else the
        ``slo_class`` deadline, else ``ServeConfig.default_deadline_ms``)
        bounds QUEUE+SERVE time: an expired request is dropped with
        :class:`DeadlineExceeded` instead of served late.

        ``tenant`` namespaces the prefix cache (a tenant's cached
        prefixes are invisible to every other tenant) and bills the
        request against the tenant's admission quota; ``slo_class``
        must be a declared ``ServeConfig.slo_classes`` name and sets
        this request's default deadline, plus — in continuous-decode
        mode — its queue priority (one-shot batch formation stays
        FIFO/group-keyed; only the class deadline applies there).

        ``rec`` is the fleet's lifecycle record when this submit is a
        failover hop (the record accumulates across hops); standalone
        submits get a fresh one (None with the obs layer disabled).
        """
        t_sub = time.perf_counter()
        sc = self._config.serve_config
        if self._faults is not None:
            # chaos hook: an armed `saturate` fault sheds here, exactly
            # like a full queue would (ServeOverloaded, retryable)
            self._faults.on_admission(self.replica_id)
        slo_rank, slo_ddl_ms = sc.resolve_slo_class(slo_class)
        ddl_ms = (deadline_ms if deadline_ms is not None
                  else slo_ddl_ms if slo_ddl_ms is not None
                  else sc.default_deadline_ms)
        deadline = (time.perf_counter() + float(ddl_ms) / 1e3
                    if ddl_ms is not None else None)
        if self._scheduler is not None:
            req = self._scheduler.make_request(feed, deadline,
                                               max_new_tokens,
                                               tenant=tenant,
                                               slo_rank=slo_rank)
        else:
            req = self._make_one_shot_request(feed, deadline,
                                              tenant=tenant,
                                              slo_rank=slo_rank)
        if rec is None and obs_state.enabled:
            rec = reqtrace.RequestRecord(req.id, t0=t_sub,
                                         deadline=deadline,
                                         ring=self.reqtrace)
        if rec is not None:
            req.rec = rec
            rec.note_hop(self.replica_id)
            rec.mark("queue_wait")
        self._requests.inc()
        try:
            self._queue.put(req)  # raises ServeOverloaded / ServeClosed
        except ServeError as e:
            if rec is not None:
                # the refused placement never held the request: keep
                # the hop trail consistent with the fleet's
                # replicas-actually-placed-on list
                rec.drop_hop()
                # a replica-level shed is retryable at the fleet tier —
                # only a standalone record finalizes here
                rec.attempt_failed("shed" if isinstance(
                    e, ServeOverloaded) else "closed")
            raise
        if self._scheduler is not None:
            self._scheduler.kick()
        return req

    def request_records(self, last: Optional[int] = None):
        """Snapshots of recently completed request lifecycle records
        (tools/serve_report.py reads these)."""
        return self.reqtrace.records(last)

    def prefix_stats(self) -> Optional[Dict[str, Any]]:
        """The prefix cache's own snapshot (entries, cached pages,
        pinned entries, insertions/evictions); None in one-shot mode
        or with ``ServeConfig.prefix_cache`` off."""
        if self._scheduler is None:
            return None
        return self._scheduler.prefix_stats()

    # -- disaggregated prefill/decode (ISSUE 19, serve/disagg.py) ----------

    def prefill_only(self, feed: Dict[str, Any]):
        """Run ONLY the prefill for one request, on the CALLER's thread
        — the disaggregated prefill pool's work unit. Returns
        ``(prepared_feed, prefix_key, request_state)``: the feed padded
        onto the program's fixed shapes, the radix key the result is
        cacheable under, and the prefill request state (device arrays —
        :func:`~parallax_tpu.serve.disagg.export_prefill` turns them
        into wire bytes). Rides the SAME jitted prefill the scheduler
        warmed at construction (identical single-request signature), so
        it never compiles at serve time; jit dispatch is thread-safe
        against the concurrently-running decode loop."""
        if self._scheduler is None:
            raise ValueError(
                "prefill_only requires continuous-decode mode "
                "(program=...)")
        prog = self._scheduler._program
        if not hasattr(prog, "prefix_key"):
            raise ValueError(
                "prefill_only requires a program exposing prefix_key "
                "(the transfer protocol is keyed by it)")
        if self._faults is not None:
            # chaos hook: an armed crash on this replica fires on the
            # prefill path too (the disagg kill-mid-transfer case)
            self._faults.on_dispatch(self.replica_id)
        if not self._scheduler.alive:
            raise ReplicaUnavailable(
                f"prefill replica {self.replica_id!r} is dead")
        prepared = prog.prepare_feed(feed)
        chunks = int(getattr(prog, "num_prefill_chunks", 1))
        with trace.span("serve.prefill_export", chunks=chunks):
            if chunks > 1:
                carry = prepared
                for k in range(chunks):
                    carry = prog.prefill_chunk(self._params, carry, k)
                rs = carry
            else:
                rs = prog.prefill(self._params, prepared)
            jax.block_until_ready(jax.tree_util.tree_leaves(rs))
        return prepared, prog.prefix_key(prepared), rs

    def import_prefix_entry(self, tenant, key, request_state,
                            positions: int = 0) -> bool:
        """Install an externally-prefilled request state into this
        replica's prefix cache (the decode side of the page-transfer
        protocol); see
        :meth:`~parallax_tpu.serve.continuous.ContinuousScheduler.
        import_prefix`. Thread-safe."""
        if self._scheduler is None:
            raise ValueError(
                "import_prefix_entry requires continuous-decode mode "
                "(program=...)")
        return self._scheduler.import_prefix(tenant, key, request_state,
                                             positions=positions)

    def _make_one_shot_request(self, feed, deadline, tenant=None,
                               slo_rank: int = 0) -> Request:
        feed = {k: np.asarray(v) for k, v in feed.items()}
        if set(feed) != set(self._example):
            raise ValueError(
                f"feed names {sorted(feed)} != example names "
                f"{sorted(self._example)}")
        if self._ragged:
            lb = self._config.serve_config.length_buckets
            longest = max(feed[n].shape[0] for n in self._ragged)
            L = bucketing.length_bucket(longest, lb)
            if L is None:
                raise ValueError(
                    f"request length {longest} exceeds the largest "
                    f"declared length bucket {lb[-1]}")
            for name in self._ragged:
                feed[name] = bucketing.pad_axis0(feed[name], L,
                                                 self._pad_value)
        # requests in one device batch must share a signature
        group_key = bucketing.batch_signature(feed)
        if group_key not in self._admitted:
            raise ValueError(
                f"request signature {[(n, s) for n, s, _ in group_key]} "
                f"is outside the declared serving set "
                f"{sorted([(n, s) for n, s, _ in sig] for sig in self._admitted)}; "
                f"serving it would compile at serve time — fix the "
                f"feed shapes or declare matching length_buckets")
        return Request(feed, deadline=deadline, group_key=group_key,
                       tenant=tenant, slo_rank=slo_rank)

    def _on_deadline_breach(self, n: int = 1,
                            where: str = "queue") -> None:
        """SLO-breach hook: every deadline expiry (queued, at dispatch,
        or during service) triggers one rate-limited flight dump with
        the serve.* metrics in-artifact."""
        if self._flight is not None:
            self._flight.trigger(
                "serve_deadline_breach",
                {"where": where, "n": int(n),
                 "timeouts_total": self.metrics.counter(
                     "serve.timeouts").value})

    # -- dispatch (batcher thread) ----------------------------------------

    def _run_batch(self, requests) -> None:
        t_host0 = time.perf_counter()
        fault_mode = (self._faults.on_dispatch(self.replica_id)
                      if self._faults is not None else None)
        # deadline re-check at dispatch: form_group sheds while
        # requests WAIT, but one can expire between dequeue and here —
        # don't spend device time on a caller who already gave up
        live = []
        n_expired = 0
        for r in requests:
            if r.deadline is not None and t_host0 > r.deadline:
                self.metrics.counter("serve.timeouts").inc()
                n_expired += 1
                r._fail(DeadlineExceeded(
                    f"request {r.id} deadline expired at dispatch"))
            else:
                live.append(r)
        if n_expired:
            self._on_deadline_breach(n_expired, where="dispatch")
        requests = live
        if not requests:
            return
        for r in requests:
            if r.rec is not None:
                # one-shot service phase: batch formation + H2D +
                # device step + result split, ended by _complete/_fail
                r.rec.mark("service", t_host0)
        n = len(requests)
        bucket = next(b for b in self._batch_buckets if b >= n)
        batch = {}
        for name in requests[0].feed:
            rows = [r.feed[name] for r in requests]
            if n < bucket:
                # edge-pad with the last real request's row (finite for
                # finite data; padded rows are discarded at split time)
                rows = rows + [rows[-1]] * (bucket - n)
            batch[name] = np.stack(rows)
        sig = bucketing.batch_signature(batch)
        exe = self._executables.get(sig)
        t_form = time.perf_counter()
        with trace.span("serve.h2d_place", bucket=bucket):
            placed = engine_lib.place_host_batch(
                self.mesh, batch,
                default_sharding_fn=self._batch_sharding_fn(bucket))
        t_host1 = time.perf_counter()
        # H2D is the feed path (any inference pays it, batched or
        # not) — recorded on its own, NOT as batcher overhead
        self._h2d_ms.record((t_host1 - t_form) * 1e3)
        with trace.span("serve.infer", n=n, bucket=bucket):
            if exe is not None:
                out = exe(self._params, placed)
            else:
                # a serve-time compile: the signature set was supposed
                # to be closed — count it loudly, serve the request
                # anyway through the jit path
                self._recompiles.inc()
                parallax_log.warning(
                    "serve dispatch missed the AOT executable table "
                    "(signature %s); compiling at serve time — declare "
                    "batch/length buckets covering this shape",
                    [(k, s) for k, s, _ in sig])
                out = self._infer_jit(self._params, placed)
            host = jax.tree.map(np.asarray, out)  # block: result ready
        if fault_mode == "nan":
            # injected silent corruption: every float leaf becomes NaN
            # AFTER the device step (serve/faults.py)
            host = jax.tree.map(
                lambda a: (np.full_like(a, np.nan)
                           if np.issubdtype(np.asarray(a).dtype,
                                            np.floating) else a), host)
        if self._check_outputs and any(
                np.issubdtype(np.asarray(a).dtype, np.floating)
                and not np.all(np.isfinite(a))
                for a in jax.tree_util.tree_leaves(host)):
            # non-finite output is a replica-health incident, not a
            # result: fail the batch with the RETRYABLE error (a fleet
            # re-serves it on a healthy replica) and let on_error feed
            # the router's error-rate probe via the batcher
            self.metrics.counter("serve.nonfinite_batches").inc()
            raise ReplicaUnavailable(
                f"replica {self.replica_id!r} produced non-finite "
                f"output for a batch of {len(requests)} request(s)")
        t_step = time.perf_counter() - t_host1
        t_host2 = time.perf_counter()
        now = t_host2
        # split once at the leaf level (one flatten for the whole
        # batch, not one tree traversal per request)
        leaves, treedef = jax.tree_util.tree_flatten(host)
        batched = [np.ndim(a) >= 1 for a in leaves]
        delivered = 0
        n_late = 0
        for i, r in enumerate(requests):
            if r.deadline is not None and now > r.deadline:
                # the step itself overran the budget: the deadline
                # contract is "meet it or shed it", so a late result
                # is DROPPED, never delivered (counted as a timeout)
                self.metrics.counter("serve.timeouts").inc()
                n_late += 1
                r._fail(DeadlineExceeded(
                    f"request {r.id} missed its deadline by "
                    f"{(now - r.deadline) * 1e3:.1f}ms during service"))
                continue
            r._complete(jax.tree_util.tree_unflatten(
                treedef, [a[i] if s else a
                          for a, s in zip(leaves, batched)]))
            delivered += 1
            self._latency.record((now - r.t_enqueue) * 1e3)
            trace.record_span(
                "serve.request", r.t_enqueue, now, id=r.id,
                batch=bucket, replica=self.replica_id,
                rid=(r.rec.key if r.rec is not None else r.id),
                hops=(len(r.rec.hops) if r.rec is not None else 1))
        if n_late:
            self._on_deadline_breach(n_late, where="service")
        self._completed.inc(delivered)
        self._batches.inc()
        self._occupancy.record(n / bucket)
        self._step_ms.record(t_step * 1e3)
        # the batching layer's own host cost on the dispatch path:
        # batch formation (stack/pad, signature, executable lookup) +
        # result split + bookkeeping — everything this call does
        # beyond the feed path (h2d above) and the device step; the
        # number tools/check_serve_slo.py holds to <=5% of step
        # wall-time
        self._batcher_ms.record(
            ((t_form - t_host0)
             + (time.perf_counter() - t_host2)) * 1e3)

    # -- live weight hot-swap (ISSUE 7) ------------------------------------

    def swap_params(self, params) -> None:
        """Replace the served parameters IN PLACE — the live-weight
        hot-swap primitive under :meth:`ServeFleet.push_weights`.

        The new pytree must match the old one structurally (same
        treedef, leaf shapes and dtypes) and is placed with the OLD
        leaves' exact shardings on the SAME mesh, so every AOT
        executable compiled at construction remains valid: the swap
        costs one ``device_put``, never a recompile
        (``serve.recompiles`` stays 0 across it). A mismatch is
        REFUSED loudly — serving through stale executables with
        reshaped weights would be undefined behavior, not an upgrade.

        The parameter reference is read once per dispatch, so the swap
        is atomic at a batch/iteration boundary; to guarantee no
        *sequence* mixes weights mid-decode, quiesce first (the fleet
        rotates the replica out of placement and waits for
        :meth:`idle`). Counted in ``serve.hotswaps``.
        """
        old = self._params
        old_leaves, old_def = jax.tree_util.tree_flatten(old)
        new_leaves, new_def = jax.tree_util.tree_flatten(params)
        if old_def != new_def:
            raise ValueError(
                "swap_params: new params tree structure differs from "
                f"the served one ({new_def} vs {old_def})")
        for i, (a, b) in enumerate(zip(old_leaves, new_leaves)):
            if (np.shape(a) != np.shape(b)
                    or engine_lib._dtype_of(a) != engine_lib._dtype_of(b)):
                raise ValueError(
                    f"swap_params: leaf {i} changed "
                    f"{np.shape(a)}/{engine_lib._dtype_of(a)} -> "
                    f"{np.shape(b)}/{engine_lib._dtype_of(b)}; the AOT "
                    f"executable set would be invalidated — rebuild "
                    f"the session for a different architecture")
        shardings = jax.tree_util.tree_unflatten(
            old_def, [x.sharding for x in old_leaves])
        with trace.span("serve.hotswap"):
            placed = jax.device_put(params, shardings)
            jax.block_until_ready(jax.tree_util.tree_leaves(placed))
        self._params = placed
        if self._scheduler is not None:
            self._scheduler.set_params(placed)
        self.metrics.counter("serve.hotswaps").inc()
        parallax_log.info("serve: hot-swapped params on replica %r "
                          "(%d leaves, zero recompiles)",
                          self.replica_id, len(new_leaves))

    # -- fleet probes ------------------------------------------------------

    @property
    def alive(self) -> bool:
        """False once the dispatch loop died (fatal fault); a dead
        replica sheds at admission (its queue is closed)."""
        if self._scheduler is not None:
            return self._scheduler.alive
        return self._batcher is None or self._batcher.alive

    @property
    def heartbeat(self) -> float:
        """``perf_counter`` time of the dispatch loop's last pass —
        stale while a step stalls (the router's straggler probe)."""
        if self._scheduler is not None:
            return self._scheduler.heartbeat
        return self._batcher.heartbeat

    def load(self) -> float:
        """Queued + in-flight work, the router's placement score."""
        n = float(len(self._queue))
        if self._scheduler is not None:
            n += self._scheduler._active() + len(self._scheduler._pending)
        elif self._batcher is not None and self._batcher.busy:
            n += 1.0
        return n

    def idle(self) -> bool:
        """Nothing queued and nothing in flight — the quiesced state a
        hot-swap requires."""
        if self._scheduler is not None:
            return self._scheduler.idle()
        return len(self._queue) == 0 and not (
            self._batcher is not None and self._batcher.busy)

    # -- introspection / teardown -----------------------------------------

    def stats(self) -> Dict[str, Any]:
        """JSON-ready snapshot of every ``serve.*`` metric."""
        return {k: v for k, v in self.metrics.snapshot().items()
                if k.startswith("serve.")}

    def close(self, drain: bool = True) -> None:
        """Stop admission; with ``drain`` (default) serve the accepted
        queue to completion (bounded by
        ``ServeConfig.drain_timeout_s``), then fail whatever remains
        with :class:`ServeClosed`. Idempotent."""
        with self._close_lock:
            if self._closed:
                return
            self._closed = True
        sc = self._config.serve_config
        self._queue.close()
        timeout = sc.drain_timeout_s if drain else 0.0
        if self._scheduler is not None:
            self._scheduler.drain(timeout)
        elif self._batcher is not None:
            self._batcher.drain(timeout)
        n = self._queue.fail_all(ServeClosed("session closed"))
        if n:
            parallax_log.warning(
                "serve close: failed %d undrained request(s)", n)

    def __enter__(self) -> "ServeSession":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


__all__ = ["ServeSession", "ServeError"]
