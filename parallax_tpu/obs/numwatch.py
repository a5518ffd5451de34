"""Numerics observatory — per-layer gradient/param health, NaN
provenance forensics, and kernel-drift sentinels (ISSUE 17).

Three instruments, all riding the PR-2 obs substrate:

**Per-layer tree stats** (``tree_prefix_stats`` / ``step_numerics``):
one fused in-graph reduction the engine appends to the step outputs,
computing per param-tree prefix ("layer") the grad norm/absmax,
non-finite count, bf16 underflow fraction (nonzero grad entries below
bf16 round-off of the layer's absmax — entries a bf16 accumulation
swallows, the PR-14 cotangent-accumulation hazard class),
param norm, and update ratio ``‖Δw‖/‖w‖``. Sampling is gated *inside*
the graph (``lax.cond`` on ``step % interval == 0``, forced on any
non-finite loss/grad so the trip step always carries a full snapshot)
because the AOT executables need a static output structure — off-steps
ship a zeros tree plus a ``_sampled=0`` flag the host consumer drops.

**NumericsMonitor**: the lazy host-side consumer (same
park-then-drain discipline as ``obs/health.py`` — ``observe`` never
blocks dispatch on device values; readings drain when ready or at the
pending cap). Consumed samples become ``numerics.<layer>.<stat>``
gauges, a bounded stats *trail* (the forensics lead-in), chrome-trace
lanes, and anomaly-detector feeds over update-ratio / underflow trends
(which in turn drive ``HealthMonitor``'s instability score — the hook
ROADMAP item 4's preemption-aware checkpoint cadence consumes).

**NaN provenance** (``provenance_report``): when the PR-8 auto-rollback
trips, the session replays the cached offending batch through a
dataflow-ordered finite sweep — input feeds, then the (pre-rollback,
already-poisoned) param tree per prefix, then the trip step's in-graph
grad stats, then the loss — and names the FIRST non-finite item
(``feed/x``, ``param/w``, ``grad/decoder``, ``loss``). The
``nonfinite_rollback`` flight artifact carries that blast-radius report
plus the stats trail leading in. No model re-execution is needed: the
forced-on-trip in-graph sample above IS the instrumented replay's
per-layer evidence, captured on the step that tripped.

**Drift sentinels** (``DriftSentinel`` + the built-in pairs): periodic
shadow-evals comparing each hand-built Pallas executor against its
reference on live shapes — the PR-14 LSTM backward kernel vs the
residual-``scan`` executor (weight gradients), the PR-16 paged-attn
``kernel`` vs the ``einsum`` path (decode outputs) — exporting
rel-error / argmax-flip gauges so a silent kernel regression pages
instead of shipping. Argmax flips are margin-aware: a flip only counts
where the reference's top-2 margin exceeds ``argmax_margin``, so the
~2^-9 benign score noise PR 16 documented cannot flap the gauge. Off
TPU both sides run under Pallas ``interpret=True`` — rel-error numbers
are CPU-relative evidence of *agreement*, not TPU lowering proof.

Everything here honors the process-wide killswitch: with
``PARALLAX_OBS=0`` the engine emits no extra step outputs and the
session constructs no monitor (structurally asserted by
``tools/check_obs_overhead.py``).
"""

from __future__ import annotations

import collections
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from parallax_tpu.obs import _state, trace
from parallax_tpu.obs.metrics import MetricsRegistry

# Per-prefix stat names, in the order they are documented. Keys of the
# inner dict of tree_prefix_stats(); also the gauge suffixes.
STAT_NAMES = ("grad_norm", "grad_absmax", "nonfinite", "underflow_frac",
              "param_norm", "update_ratio")

# Flag leaf marking whether the in-graph cond actually computed stats
# this step (1.0) or shipped the structural zeros tree (0.0).
SAMPLED_KEY = "_sampled"

_EPS = 1e-12


# ---------------------------------------------------------------------------
# prefix grouping
# ---------------------------------------------------------------------------

def _path_entry(k) -> str:
    if hasattr(k, "key"):
        return str(k.key)
    if hasattr(k, "name"):
        return str(k.name)
    if hasattr(k, "idx"):
        return str(k.idx)
    return str(k)


def _prefix_of(path) -> str:
    """Layer name of one leaf: the first component of its tree path.

    Local on purpose — importing core.classify here would cycle
    obs <-> core (the engine imports this module)."""
    if not path:
        return "<root>"
    return _path_entry(path[0])


def _leaf_name(path) -> str:
    if not path:
        return "<root>"
    return "/".join(_path_entry(k) for k in path)


def _grouped(params_before, params_after, grads):
    """Zip the three trees leaf-wise, grouped by top-level prefix.

    params_before/params_after share one treedef and optax grads match
    it, so flatten order is aligned across all three. Non-inexact
    leaves (int slot counters riding in a param tree) carry no
    numerics signal and are skipped."""
    flat_b = jax.tree_util.tree_flatten_with_path(params_before)[0]
    flat_a = jax.tree_util.tree_leaves(params_after)
    flat_g = jax.tree_util.tree_leaves(grads)
    groups: Dict[str, List[Tuple[Any, Any, Any]]] = {}
    for (path, w0), w1, g in zip(flat_b, flat_a, flat_g):
        if not jnp.issubdtype(jnp.result_type(w0), jnp.inexact):
            continue
        groups.setdefault(_prefix_of(path), []).append((w0, w1, g))
    return groups


def stat_prefixes(params) -> List[str]:
    """Static layer-name list ``step_numerics`` will emit for this
    param tree (sorted; prefixes whose leaves are all non-inexact are
    absent)."""
    return sorted(_grouped(params, params, params))


# ---------------------------------------------------------------------------
# in-graph stats
# ---------------------------------------------------------------------------

def tree_prefix_stats(params_before, params_after, grads) -> Dict:
    """One fused reduction pass: {layer: {stat: f32 scalar}}.

    Stat definitions (per prefix, over its float leaves):
      grad_norm       l2 norm of the gradient slice
      grad_absmax     max |g| (inf/nan propagate — that is the signal)
      nonfinite       count of non-finite gradient entries
      underflow_frac  fraction of NONZERO grad entries with
                      ``|g| < 2**-8 × layer absmax`` — entries a bf16
                      accumulation against the layer's dominant
                      magnitudes swallows entirely (the PR-14
                      cotangent-accumulation hazard class). Strict
                      flush-to-BF16-zero is NOT the definition: bf16
                      shares f32's exponent range, so that region is
                      all f32 subnormals, which XLA CPU flushes in
                      comparisons anyway — structurally undetectable.
                      Exact-zero grads don't count, so a sparse layer
                      reads 0.0, not ~1.0.
      param_norm      l2 norm of the pre-update weights
      update_ratio    ‖w_after - w_before‖ / (‖w_before‖ + eps)

    Jittable; cost is a handful of elementwise+reduce ops per layer,
    fused by XLA into the step it rides in.
    """
    out: Dict[str, Dict[str, jnp.ndarray]] = {}
    bf16_round = jnp.float32(2.0 ** -8)  # bf16 round-off threshold
    for prefix, items in sorted(_grouped(params_before, params_after,
                                         grads).items()):
        g_absmax = jnp.float32(0.0)
        for _w0, _w1, g in items:
            g_absmax = jnp.maximum(
                g_absmax, jnp.max(jnp.abs(jnp.asarray(g, jnp.float32))))
        under_thresh = bf16_round * g_absmax
        g_sq = jnp.float32(0.0)
        g_bad = jnp.float32(0.0)
        g_nz = jnp.float32(0.0)
        g_under = jnp.float32(0.0)
        w_sq = jnp.float32(0.0)
        d_sq = jnp.float32(0.0)
        for w0, w1, g in items:
            gf = jnp.asarray(g, jnp.float32)
            w0f = jnp.asarray(w0, jnp.float32)
            w1f = jnp.asarray(w1, jnp.float32)
            g_sq = g_sq + jnp.sum(jnp.square(gf))
            g_bad = g_bad + jnp.sum(
                (~jnp.isfinite(gf)).astype(jnp.float32))
            nz = gf != 0
            g_nz = g_nz + jnp.sum(nz.astype(jnp.float32))
            g_under = g_under + jnp.sum(
                (nz & (jnp.abs(gf) < under_thresh)).astype(jnp.float32))
            w_sq = w_sq + jnp.sum(jnp.square(w0f))
            d_sq = d_sq + jnp.sum(jnp.square(w1f - w0f))
        w_norm = jnp.sqrt(w_sq)
        out[prefix] = {
            "grad_norm": jnp.sqrt(g_sq),
            "grad_absmax": g_absmax,
            "nonfinite": g_bad,
            "underflow_frac": g_under / jnp.maximum(g_nz, 1.0),
            "param_norm": w_norm,
            "update_ratio": jnp.sqrt(d_sq) / (w_norm + _EPS),
        }
    return out


def step_numerics(params_before, params_after, grads, *, step,
                  interval: int, force=None) -> Dict:
    """The engine-side hook: stats tree under an in-graph sampling gate.

    Computes ``tree_prefix_stats`` only when ``step % interval == 0``
    OR ``force`` (the engine passes non-finite-loss/grad, so a trip
    step ALWAYS carries real stats — this is what makes the provenance
    replay free). The off-branch ships a structurally identical zeros
    tree; ``_sampled`` (1.0/0.0) tells the host consumer which it got.
    """
    if interval <= 0:
        raise ValueError(f"numerics interval must be > 0, got {interval}")
    sampled = (jnp.asarray(step) % interval) == 0
    if force is not None:
        sampled = sampled | force
    prefixes = stat_prefixes(params_before)

    def _compute(_):
        t = tree_prefix_stats(params_before, params_after, grads)
        t[SAMPLED_KEY] = jnp.float32(1.0)
        return t

    def _zeros(_):
        t: Dict[str, Any] = {
            p: {s: jnp.float32(0.0) for s in STAT_NAMES}
            for p in prefixes}
        t[SAMPLED_KEY] = jnp.float32(0.0)
        return t

    return jax.lax.cond(sampled, _compute, _zeros, None)


# ---------------------------------------------------------------------------
# host-side lazy consumer
# ---------------------------------------------------------------------------

def _tree_ready(tree) -> bool:
    for leaf in jax.tree_util.tree_leaves(tree):
        is_ready = getattr(leaf, "is_ready", None)
        if is_ready is not None and not is_ready():
            return False
    return True


def _sample_ready(stats) -> bool:
    """Whether ``_consume`` can read ``stats`` without blocking. Every
    leaf is an output of the one step that made the sample, and a
    step's outputs become ready together, so the flag answers for the
    whole tree without a walk over it (this runs on every step)."""
    flag = stats.get(SAMPLED_KEY)
    if flag is None:
        return _tree_ready(stats)
    is_ready = getattr(flag, "is_ready", None)
    return is_ready is None or is_ready()


class NumericsMonitor:
    """Lazy consumer of the in-graph samples (obs/health.py pattern).

    ``observe(step, outputs['numerics'])`` parks the device tree and
    returns immediately; pending samples drain when their buffers are
    ready (or, past ``max_pending``, blocking — bounded memory beats
    unbounded laziness). Consumed samples become
    ``numerics.<layer>.<stat>`` gauges, a bounded trail (the forensics
    lead-in), one ``numerics.sample`` chrome lane per consume, and
    anomaly feeds on ``numerics.<layer>.update_ratio`` /
    ``.underflow_frac``.

    Bookkeeping (``total_samples`` / ``total_skipped``) is plain-int,
    NOT registry counters, so it stays correct if the killswitch
    toggles mid-run — same opt-out-consistency reasoning as
    HealthMonitor's.
    """

    def __init__(self, registry: MetricsRegistry, interval: int, *,
                 anomaly=None, on_sample: Optional[Callable] = None,
                 trail_capacity: int = 64, max_pending: int = 64):
        self.registry = registry
        self.interval = int(interval)
        self.anomaly = anomaly
        self.on_sample = on_sample
        self.total_samples = 0
        self.total_skipped = 0
        self.last_step: Optional[int] = None
        self.last_stats: Optional[Dict[str, Dict[str, float]]] = None
        self._trail: collections.deque = collections.deque(
            maxlen=trail_capacity)
        self._pending: collections.deque = collections.deque()
        self._max_pending = max_pending
        # gauge objects cached per (layer, stat): the consume path
        # runs on the dispatch thread every sampled step — no f-string
        # + registry-lock round trip per stat there
        self._gauges: Dict[Tuple[str, str], Any] = {}
        # RLock: a flight provider can fire from inside a consume
        # callback path without deadlocking (HealthMonitor precedent).
        self._lock = threading.RLock()

    def observe(self, step: int, stats) -> None:
        if not _state.enabled or stats is None:
            return
        with self._lock:
            self._pending.append((int(step), stats))
            self._drain(block=len(self._pending) > self._max_pending)

    def poll(self, block: bool = False) -> None:
        """Drain pending samples; ``block=True`` waits for all."""
        if not _state.enabled:
            return
        with self._lock:
            self._drain(block=block)

    def _drain(self, block: bool) -> None:
        while self._pending:
            step, stats = self._pending[0]
            if not block and not _sample_ready(stats):
                return
            self._pending.popleft()
            try:
                self._consume(step, stats)
            except Exception:
                # one poisoned buffer must not wedge the trail
                self.total_skipped += 1

    def _consume(self, step: int, stats) -> None:
        t0 = time.perf_counter()
        # flag first: the off-step skip path (most steps) must touch
        # ONE scalar, not materialize the whole zeros tree
        flag = stats.get(SAMPLED_KEY)
        if flag is not None and float(flag) < 0.5:
            self.total_skipped += 1
            return
        host: Dict[str, Dict[str, float]] = {}
        for key, val in stats.items():
            if key != SAMPLED_KEY:
                host[key] = {s: float(v) for s, v in val.items()}
        self.total_samples += 1
        self.last_step = step
        self.last_stats = host
        self._trail.append({"step": step, "stats": host})
        self.registry.counter("numerics.samples").inc()
        worst_ur = 0.0
        bad_layers = 0
        gauges = self._gauges
        for prefix, vals in host.items():
            for s, v in vals.items():
                g = gauges.get((prefix, s))
                if g is None:
                    g = gauges[(prefix, s)] = self.registry.gauge(
                        f"numerics.{prefix}.{s}")
                g.set(v)
            worst_ur = max(worst_ur, vals["update_ratio"])
            if vals["nonfinite"] > 0:
                bad_layers += 1
            if self.anomaly is not None:
                self.anomaly.observe(f"numerics.{prefix}.update_ratio",
                                     step, vals["update_ratio"])
                self.anomaly.observe(f"numerics.{prefix}.underflow_frac",
                                     step, vals["underflow_frac"])
        if self.on_sample is not None:
            self.on_sample(step, host)
        trace.record_span("numerics.sample", t0, time.perf_counter(),
                          step=step, layers=len(host),
                          worst_update_ratio=round(worst_ur, 6),
                          nonfinite_layers=bad_layers)

    # -- forensics / reporting ------------------------------------------

    def trail(self) -> List[Dict]:
        with self._lock:
            return list(self._trail)

    def trail_tail(self, n: int = 16) -> List[Dict]:
        with self._lock:
            return list(self._trail)[-n:]

    def report(self) -> Dict:
        """Blocking summary (close/CLI path): drains pending first."""
        self.poll(block=True)
        with self._lock:
            return {
                "interval": self.interval,
                "samples": self.total_samples,
                "skipped": self.total_skipped,
                "last_step": self.last_step,
                "layers": self.last_stats,
            }

    def snapshot_for_dump(self) -> Dict:
        """Non-blocking flight section — a dump on a wedged device
        must not hang draining pending samples."""
        with self._lock:
            return {
                "interval": self.interval,
                "samples": self.total_samples,
                "skipped": self.total_skipped,
                "pending": len(self._pending),
                "last_step": self.last_step,
                "trail": list(self._trail),
            }


# ---------------------------------------------------------------------------
# NaN provenance
# ---------------------------------------------------------------------------

def _scan_array(name: str, arr) -> Dict:
    a = np.asarray(arr)
    if not np.issubdtype(a.dtype, np.floating):
        return {"name": name, "size": int(a.size), "nonfinite": 0}
    bad = int(a.size - np.count_nonzero(np.isfinite(a)))
    entry = {"name": name, "size": int(a.size), "nonfinite": bad}
    if bad:
        entry["finite_frac"] = round(1.0 - bad / max(a.size, 1), 6)
    return entry


def provenance_report(*, feeds=None, params=None, trip_stats=None,
                      loss=None, step=None, kind=None) -> Dict:
    """Blast-radius report naming the first non-finite item.

    The sweep follows dataflow order — the earliest poisoned stage is
    the root cause, everything after it is blast radius:

      1. ``feed/<key>``  — the cached offending batch's input arrays
      2. ``param/<layer>`` — the live (pre-rollback, so already
         poisoned if the optimizer applied a NaN update) weight tree
      3. ``grad/<layer>`` — non-finite counts from the trip step's
         forced in-graph sample (the instrumented replay's per-layer
         evidence; no model re-execution)
      4. ``loss``

    Blocking (np.asarray on device values) — this only runs on the
    incident path, where the rollback is already stalling dispatch.
    """
    checks: List[Dict] = []
    if feeds is not None:
        flat = jax.tree_util.tree_flatten_with_path(feeds)[0]
        for path, leaf in sorted(flat, key=lambda kv: _leaf_name(kv[0])):
            checks.append(_scan_array(f"feed/{_leaf_name(path)}", leaf))
    if params is not None:
        flat = jax.tree_util.tree_flatten_with_path(params)[0]
        groups: Dict[str, int] = {}
        sizes: Dict[str, int] = {}
        for path, leaf in flat:
            if not jnp.issubdtype(jnp.result_type(leaf), jnp.inexact):
                continue
            entry = _scan_array("", leaf)
            p = _prefix_of(path)
            groups[p] = groups.get(p, 0) + entry["nonfinite"]
            sizes[p] = sizes.get(p, 0) + entry["size"]
        for p in sorted(groups):
            e = {"name": f"param/{p}", "size": sizes[p],
                 "nonfinite": groups[p]}
            if groups[p]:
                e["finite_frac"] = round(
                    1.0 - groups[p] / max(sizes[p], 1), 6)
            checks.append(e)
    trip_sampled = False
    if trip_stats is not None:
        host = {k: v for k, v in trip_stats.items() if k != SAMPLED_KEY}
        flag = trip_stats.get(SAMPLED_KEY)
        trip_sampled = (flag is None
                        or float(np.asarray(flag)) >= 0.5)
        if trip_sampled:
            for prefix in sorted(host):
                bad = int(float(np.asarray(host[prefix]["nonfinite"])))
                checks.append({"name": f"grad/{prefix}",
                               "nonfinite": bad,
                               "grad_absmax": float(
                                   np.asarray(host[prefix]["grad_absmax"]))})
    if loss is not None:
        checks.append(_scan_array("loss", loss))
    culprit = next((c["name"] for c in checks if c["nonfinite"] > 0), None)
    return {
        "step": step,
        "kind": kind,
        "order": "feeds -> params -> grads -> loss",
        "culprit": culprit,
        "blast_radius": sum(1 for c in checks if c["nonfinite"] > 0),
        "trip_stats_sampled": trip_sampled,
        "checks": checks,
    }


# ---------------------------------------------------------------------------
# drift sentinels
# ---------------------------------------------------------------------------

class DriftSentinel:
    """Shadow-eval one kernel executor against its reference.

    ``pair_fn()`` returns ``(candidate, reference)`` arrays computed on
    live shapes; ``check()`` prices the disagreement:

      rel_err          max |cand - ref| / (max |ref| + eps)
      argmax_flip_frac fraction of rows (last axis) whose argmax
                       differs AND whose reference top-2 margin exceeds
                       ``argmax_margin`` — benign ~2^-9 tie noise
                       (PR 16) cannot flap the gauge
      nonfinite        non-finite entries in the candidate

    A check is ``flagged`` when rel_err > rel_err_tol, any margin-aware
    argmax flips, or any non-finite output. Gauges land as
    ``numerics.drift.<name>.{rel_err, accuracy, argmax_flip_frac}``
    with check/alert counters; ``accuracy = 1/(1+rel_err)`` sits at
    ~1.0 and only moves on real drift, which is what the regression
    gate ratios against (a raw 1e-6 rel_err would ratio-noise across
    runs).
    """

    def __init__(self, name: str, pair_fn: Callable[[], Tuple], *,
                 registry: Optional[MetricsRegistry] = None,
                 rel_err_tol: float = 1e-2,
                 argmax_axis: Optional[int] = None,
                 argmax_margin: float = 1e-4):
        self.name = name
        self.pair_fn = pair_fn
        self.registry = registry
        self.rel_err_tol = float(rel_err_tol)
        self.argmax_axis = argmax_axis
        self.argmax_margin = float(argmax_margin)
        self.last_result: Optional[Dict] = None

    def check(self) -> Dict:
        t0 = time.perf_counter()
        cand, ref = self.pair_fn()
        cand = np.asarray(cand, np.float64)
        ref = np.asarray(ref, np.float64)
        denom = float(np.max(np.abs(ref))) + _EPS
        diff = float(np.max(np.abs(cand - ref)))
        rel_err = diff / denom
        nonfinite = int(cand.size - np.count_nonzero(np.isfinite(cand)))
        flips = None
        if self.argmax_axis is not None and cand.ndim >= 1 \
                and cand.shape[self.argmax_axis] >= 2:
            ai_c = np.argmax(cand, axis=self.argmax_axis)
            ai_r = np.argmax(ref, axis=self.argmax_axis)
            srt = np.sort(ref, axis=self.argmax_axis)
            margin = (np.take(srt, -1, axis=self.argmax_axis)
                      - np.take(srt, -2, axis=self.argmax_axis))
            flips = float(np.mean((ai_c != ai_r)
                                  & (margin > self.argmax_margin)))
        flagged = bool((not np.isfinite(rel_err))
                       or rel_err > self.rel_err_tol
                       or (flips or 0.0) > 0.0
                       or nonfinite > 0)
        result = {
            "name": self.name,
            "rel_err": rel_err,
            "accuracy": 1.0 / (1.0 + rel_err),
            "argmax_flip_frac": flips,
            "nonfinite": nonfinite,
            "rel_err_tol": self.rel_err_tol,
            "flagged": flagged,
            "check_ms": round((time.perf_counter() - t0) * 1e3, 3),
        }
        self.last_result = result
        if self.registry is not None and _state.enabled:
            base = f"numerics.drift.{self.name}"
            self.registry.gauge(f"{base}.rel_err").set(rel_err)
            self.registry.gauge(f"{base}.accuracy").set(result["accuracy"])
            if flips is not None:
                self.registry.gauge(f"{base}.argmax_flip_frac").set(flips)
            self.registry.counter(f"{base}.checks").inc()
            if flagged:
                self.registry.counter(f"{base}.alerts").inc()
        trace.record_span(f"numerics.drift.{self.name}", t0,
                          time.perf_counter(),
                          rel_err=float(f"{rel_err:.3e}"),
                          flagged=flagged)
        return result


def lstm_drift_pair(T: int = 6, B: int = 8, E: int = 16, H: int = 32,
                    P: int = 16, seed: int = 0,
                    perturb: float = 0.0) -> Callable[[], Tuple]:
    """PR-14 A/B on live shapes: pallas LSTM *backward* kernel vs the
    residual-``scan`` executor, compared on the weight gradient (where
    the bf16 cotangent-accumulation hazard lived). ``perturb`` scales
    the candidate by ``1 + perturb`` — a deliberate injected drift for
    testing the sentinel itself, not the kernel."""

    def pair_fn():
        from parallax_tpu.ops import pallas_lstm
        rng = np.random.default_rng(seed)
        x = (rng.standard_normal((T, B, E)) * 0.2).astype(np.float32)
        w = (rng.standard_normal((E + P, 4 * H)) * 0.2).astype(np.float32)
        b = np.zeros((4 * H,), np.float32)
        wp = (rng.standard_normal((H, P)) * 0.2).astype(np.float32)
        g_out = rng.standard_normal((T, B, P)).astype(np.float32)

        def loss(bwd_impl):
            def f(w_):
                y = pallas_lstm.lstm_scan(
                    jnp.asarray(x), w_, jnp.asarray(b), jnp.asarray(wp),
                    impl="pallas", bwd_impl=bwd_impl, interpret=True)
                return jnp.sum(y * g_out)
            return jax.grad(f)(jnp.asarray(w))

        cand = np.asarray(loss("kernel"))
        ref = np.asarray(loss("scan"))
        if perturb:
            cand = cand * (1.0 + perturb)
        return cand, ref

    return pair_fn


def paged_attn_drift_pair(seed: int = 0,
                          perturb: float = 0.0) -> Callable[[], Tuple]:
    """PR-16 A/B on live shapes: paged-attn ``kernel`` vs ``einsum`` on
    decode outputs. Only slots with live pages are compared — a
    zero-live-page slot is kernel-defined zeros vs einsum-read clipped
    garbage, a documented non-signal."""

    def pair_fn():
        from parallax_tpu.ops import pallas_paged_attention as ppa
        S, G, D, H, ps, P, pool = 4, 3, 32, 2, 4, 4, 12
        rng = np.random.default_rng(seed)
        q = rng.standard_normal((S, G, D)).astype(np.float32) * 0.3
        k_pool = rng.standard_normal((pool, ps, D)).astype(np.float32) * 0.3
        v_pool = rng.standard_normal((pool, ps, D)).astype(np.float32) * 0.3
        pages = np.full((S, P), pool, np.int32)  # sentinel = pool
        pages[0, :4] = [0, 1, 2, 3]
        pages[1, :2] = [4, 5]
        pages[2, :1] = [6]
        pos = np.array([[13, 14, 15], [5, 6, 7], [1, 2, 3], [0, 1, 2]],
                       np.int32)
        live = 3  # slot 3 has zero live pages

        def run(impl):
            return ppa.paged_decode_attention(
                jnp.asarray(q), jnp.asarray(k_pool), jnp.asarray(v_pool),
                jnp.asarray(pages), jnp.asarray(pos),
                num_heads=H, page_size=ps, impl=impl, interpret=True)

        cand = np.asarray(run("kernel"))[:live]
        ref = np.asarray(run("einsum"))[:live]
        if perturb:
            cand = cand * (1.0 + perturb)
        return cand, ref

    return pair_fn


def default_sentinels(registry: Optional[MetricsRegistry] = None,
                      perturb: float = 0.0) -> List[DriftSentinel]:
    """The two built-in executor A/Bs (names are the gauge keys the
    tests and alerts read)."""
    return [
        DriftSentinel("lstm_bwd", lstm_drift_pair(perturb=perturb),
                      registry=registry, rel_err_tol=1e-3),
        DriftSentinel("paged_attn", paged_attn_drift_pair(perturb=perturb),
                      registry=registry, rel_err_tol=1e-2,
                      argmax_axis=-1),
    ]
