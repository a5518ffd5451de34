"""HBM watch: compiled peaks, a live per-step ring, and OOM preflight.

Three memory truths, one owner:

* **Compiled peak** — what XLA's ``memory_analysis()`` says one step
  executable needs (arguments + outputs + temps − aliased/donated
  buffers): :func:`compiled_memory` on any compiled object,
  :func:`compiled_step_memory` on a live engine (prefers the warmup
  executables; otherwise pays one host-side lower+compile whose
  executable is handed to the engine's AOT table, so the next step
  reuses it instead of recompiling).
* **Live HBM** — a bounded ring of ``device_memory_stats`` samples
  taken post-dispatch (:meth:`MemWatch.sample`): bytes-in-use /
  peak-bytes / bytes-limit per device, exported as lazy ``device.*``
  registry gauges the Prometheus exporter (obs/export.py) serves, and
  an ``oom_risk`` flight incident the moment any device crosses the
  risk fraction of its limit — the page-in-the-night BEFORE the OOM,
  with the ring in the artifact showing the climb.
* **OOM preflight** — :func:`hbm_budget_bytes` resolves the per-device
  budget (TuneConfig override, else the smallest reported
  ``bytes_limit``); ``tune/search.py`` refuses any candidate plan
  whose compiled peak exceeds ``budget × hbm_headroom`` before it
  pays a measured trial.

CPU honesty: XLA:CPU reports no ``memory_stats()``, so on the tier-1
rig the live ring stays empty and the gauges are simply absent —
never fabricated. ``memory_analysis()`` DOES work on CPU, so the
compiled-peak layer (and the preflight) is fully exercised there.
Killswitch: with the obs layer disabled (``PARALLAX_OBS=0`` /
``obs.disable()``) ``sample()`` is a structural no-op — no stats
call, no ring append, no gauges.
"""

from __future__ import annotations

import collections
import threading
import time
from typing import Any, Callable, Dict, List, Optional

from parallax_tpu.common.lib import parallax_log
from parallax_tpu.obs import _state
from parallax_tpu.obs.health import device_memory_stats
from parallax_tpu.obs.metrics import MetricsRegistry

# bytes-in-use / bytes-limit fraction above which a device is flagged
# as at OOM risk (one flight incident per process, flightrec dedups)
DEFAULT_OOM_RISK_FRAC = 0.92

_MEMORY_FIELDS = ("temp_size_in_bytes", "argument_size_in_bytes",
                  "output_size_in_bytes", "alias_size_in_bytes",
                  "generated_code_size_in_bytes")


def compiled_memory(compiled) -> Optional[Dict[str, int]]:
    """``memory_analysis()`` of one compiled executable as a JSON-ready
    dict, plus the derived ``peak_bytes`` — the working-set bound the
    OOM preflight compares against a device's HBM budget:
    arguments + outputs + temps + generated code − aliased bytes
    (donated buffers are counted once, not twice). None when the
    backend doesn't expose the analysis; never raises."""
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return None
    if ma is None:
        return None
    out: Dict[str, int] = {}
    for f in _MEMORY_FIELDS:
        v = getattr(ma, f, None)
        if v is not None:
            out[f] = int(v)
    if not out:
        return None
    out["peak_bytes"] = (
        out.get("argument_size_in_bytes", 0)
        + out.get("output_size_in_bytes", 0)
        + out.get("temp_size_in_bytes", 0)
        + out.get("generated_code_size_in_bytes", 0)
        - out.get("alias_size_in_bytes", 0))
    return out


def compiled_step_memory(engine) -> Optional[Dict[str, Any]]:
    """Compiled-step memory account for a live engine.

    Prefers the already-AOT-compiled executables (``warmup()`` /
    the tuner preflight) — max ``peak_bytes`` across buckets, basis
    ``"warmup"``. Without one, pays a single host-side compile against
    the engine's real shardings (init compiled for its output
    shardings, the step lowered against sharded abstract state +
    placed-batch avals — what the first real step would lower) and hands
    the executable to the engine's AOT table so the very next step of
    that signature dispatches it instead of recompiling: the preflight
    compile is the compile the trial would have paid anyway, just
    earlier. Memoized per engine AND per AOT-table size: a
    preflight-time single-bucket account must not mask a later
    warmup's max-across-buckets peak (the OOM story is only as good
    as the biggest bucket). Returns None (never raises) when the
    backend lacks ``memory_analysis``."""
    n_exec = len(getattr(engine, "_executables", None) or {})
    memo = getattr(engine, "_memwatch_compiled", None)
    if memo is not None:
        if memo == {}:  # known-unavailable: a backend property, the
            return None  # executable count doesn't change it
        if memo.get("n_executables") == n_exec:
            return memo
    result = None
    try:
        if n_exec:
            per = {}
            for sig, compiled in engine._executables.items():
                m = compiled_memory(compiled)
                if m:
                    per[str(sig)] = m
            if per:
                worst = max(per.values(),
                            key=lambda m: m["peak_bytes"])
                result = dict(worst, basis="warmup",
                              executables=len(per))
        if result is None:
            result = _compile_for_memory(engine)
    except Exception as e:
        parallax_log.warning("compiled-step memory analysis failed: "
                             "%s", e)
        result = None
    if result is not None:
        result["n_executables"] = len(
            getattr(engine, "_executables", None) or {})
    engine._memwatch_compiled = result if result is not None else {}
    return result


def _compile_for_memory(engine) -> Optional[Dict[str, Any]]:
    """One host-side step compile with real shardings; the executable
    is stashed into the engine's AOT table (see compiled_step_memory)."""
    import jax

    from parallax_tpu.compile import bucketing

    shapes = jax.eval_shape(engine._init_jit, 0)
    shardings = engine._init_jit.lower(0).compile().output_shardings
    state = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                           sharding=sh),
        shapes, shardings)
    b = engine._example_batch_dim
    if b is None or not isinstance(engine._batch_shapes, dict):
        lowered = engine._step_jit.lower(state, engine._batch_shapes)
        return_to_table = False
        compiled = lowered.compile()
    else:
        avals = engine._bucket_avals(int(b))
        compiled = engine._step_jit.lower(state, avals).compile()
        sig = bucketing.batch_signature(avals)
        engine._executables[sig] = compiled
        engine._traced_signatures.add(sig)
        return_to_table = True
    m = compiled_memory(compiled)
    if m is None:
        return None
    return dict(m, basis="preflight", reused_as_aot=return_to_table)


def hbm_budget_bytes(tune_config=None,
                     stats_fn: Callable[[], Dict] = device_memory_stats
                     ) -> Optional[int]:
    """The per-device HBM budget the preflight judges compiled peaks
    against: an explicit ``TuneConfig.hbm_budget_gb`` wins; otherwise
    the smallest ``bytes_limit`` any local device reports. None when
    neither exists (CPU rig without an override) — the preflight then
    records itself as skipped rather than guessing."""
    if tune_config is not None \
            and getattr(tune_config, "hbm_budget_gb", None):
        return int(float(tune_config.hbm_budget_gb) * 1e9)
    try:
        stats = stats_fn() or {}
    except Exception:
        return None
    limits = [v.get("bytes_limit") for v in stats.values()
              if isinstance(v, dict) and v.get("bytes_limit")]
    return min(int(v) for v in limits) if limits else None


class MemWatch:
    """Bounded live-HBM ring + compiled peaks + oom_risk incidents.

    One instance per session; ``sample()`` runs post-dispatch on the
    dispatch thread (cost: one ``memory_stats()`` poll per local
    device, ~µs each on backends without the API — priced by
    tools/check_obs_overhead.py). ``stats_fn`` is injectable so tests
    (and the golden exporter test) run without HBM hardware.
    """

    def __init__(self, registry: Optional[MetricsRegistry] = None,
                 flight=None, capacity: int = 256, every: int = 1,
                 oom_risk_frac: float = DEFAULT_OOM_RISK_FRAC,
                 stats_fn: Callable[[], Dict] = device_memory_stats):
        if int(capacity) < 1:
            raise ValueError(
                f"memwatch capacity must be >= 1, got {capacity}")
        if int(every) < 1:
            raise ValueError(
                f"memwatch every must be >= 1, got {every}")
        if not (0.0 < float(oom_risk_frac) <= 1.0):
            raise ValueError(
                f"oom_risk_frac must be in (0, 1], got "
                f"{oom_risk_frac}")
        self.registry = registry if registry is not None \
            else MetricsRegistry()
        self._flight = flight
        self._every = int(every)
        self._frac = float(oom_risk_frac)
        self._stats_fn = stats_fn
        self._lock = threading.Lock()
        self._ring: collections.deque = collections.deque(
            maxlen=int(capacity))
        self._last: Dict[str, Dict[str, int]] = {}
        self._gauged: set = set()
        self._calls = 0
        self._total = 0
        # stats-less-backend latch: XLA:CPU answers memory_stats()
        # with None on every device, forever — after a few empty
        # polls the per-step sample collapses to one attribute check
        # instead of an N-device poll (the 2% obs budget matters)
        self._empty_polls = 0
        self._unavailable = False
        self._samples = self.registry.counter("memwatch.samples")
        self._risk_events = self.registry.counter(
            "memwatch.oom_risk_events")
        self._compiled: Optional[Dict[str, Any]] = None
        self._live_peak = 0

    @property
    def total_samples(self) -> int:
        """Lifetime ring appends (check_obs_overhead counts these —
        and asserts they stay 0 under the killswitch). Plain int, not
        the registry counter: the killswitch makes counters no-op,
        and the structural claim is that the ring itself never grew."""
        with self._lock:
            return self._total

    def sample(self, step: Optional[int] = None) -> Optional[Dict]:
        """Poll device memory once (respecting ``every``) and append
        to the ring; fires the ``oom_risk`` incident when any device
        crosses the risk fraction of its limit. Structural no-op when
        the obs layer is disabled (no stats call, no ring) or the
        backend reports nothing (CPU)."""
        if not _state.enabled or self._unavailable:
            return None
        self._calls += 1
        if (self._calls - 1) % self._every:
            return None
        try:
            stats = self._stats_fn() or {}
        except Exception:
            return None
        if not stats:
            self._empty_polls += 1
            if self._empty_polls >= 3:
                self._unavailable = True
            return None
        self._empty_polls = 0
        row = {"step": step, "ts": time.time(),
               "devices": {d: {k: int(v) for k, v in s.items()
                               if k in ("bytes_in_use",
                                        "peak_bytes_in_use",
                                        "bytes_limit")}
                           for d, s in stats.items()}}
        at_risk = []
        with self._lock:
            self._ring.append(row)
            self._total += 1
            self._last = row["devices"]
            for dev, s in row["devices"].items():
                in_use = s.get("bytes_in_use", 0)
                self._live_peak = max(self._live_peak,
                                      s.get("peak_bytes_in_use",
                                            in_use))
                limit = s.get("bytes_limit")
                if limit and in_use / limit >= self._frac:
                    at_risk.append({"device": dev,
                                    "bytes_in_use": in_use,
                                    "bytes_limit": limit,
                                    "frac": round(in_use / limit,
                                                  4)})
        self._samples.inc()
        self._register_gauges(row["devices"])
        if at_risk:
            self._risk_events.inc(len(at_risk))
            parallax_log.warning(
                "memwatch: %d device(s) above %.0f%% of HBM limit: "
                "%s", len(at_risk), self._frac * 100, at_risk)
            if self._flight is not None:
                self._flight.trigger(
                    "oom_risk", {"step": step, "devices": at_risk,
                                 "risk_frac": self._frac})
        return row

    def _register_gauges(self, devices: Dict[str, Dict]) -> None:
        """Lazy per-device gauges (``device.<dev>.bytes_in_use`` /
        ``peak_bytes`` / ``bytes_limit``) reading the latest sample —
        one registration per device ever seen, zero extra device
        polls at scrape time, served by the Prometheus exporter like
        any other gauge."""
        for dev in devices:
            if dev in self._gauged:
                continue
            self._gauged.add(dev)
            for key, field in (("bytes_in_use", "bytes_in_use"),
                               ("peak_bytes", "peak_bytes_in_use"),
                               ("bytes_limit", "bytes_limit")):
                self.registry.gauge(
                    f"device.{dev}.{key}").set_fn(
                    lambda d=dev, f=field: self._last.get(
                        d, {}).get(f))

    def capture_compiled(self, engine) -> Optional[Dict[str, Any]]:
        """Record the engine's compiled-step memory account (call at
        warmup, when the executables exist and the analysis is free);
        exported as the ``memwatch.compiled_peak_bytes`` gauge and the
        flight artifact's ``compiled`` section."""
        m = compiled_step_memory(engine)
        if m:
            self._compiled = m
            self.registry.gauge("memwatch.compiled_peak_bytes").set(
                m["peak_bytes"])
        return m

    def live_peak_bytes(self) -> Optional[int]:
        """High-water bytes-in-use across every sample so far (the
        runtime-measured side beside the compiled ``peak_bytes``);
        None when the backend never reported."""
        with self._lock:
            return self._live_peak or None

    def stats(self) -> Dict[str, Any]:
        """JSON-ready flight-recorder section: the ring, the compiled
        account, the live high-water mark and the risk counter."""
        with self._lock:
            ring = list(self._ring)
            peak = self._live_peak
        return {
            "samples": self._samples.value,
            "oom_risk_events": self._risk_events.value,
            "live_peak_bytes": peak or None,
            "compiled": self._compiled,
            "ring": ring[-32:],
        }


__all__ = ["MemWatch", "DEFAULT_OOM_RISK_FRAC", "compiled_memory",
           "compiled_step_memory", "hbm_budget_bytes"]
