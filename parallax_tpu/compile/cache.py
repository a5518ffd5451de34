"""Engine and executable caching across rebuilds and relaunches.

Two cache layers with different lifetimes:

* ``EngineCache`` (in-process): built ``Engine`` objects keyed by
  ``(plan, batch-signature)`` where plan = ``(dp, tp, run_option,
  sync, local_aggregation)`` — the session's full ``tune.Plan`` key
  (ISSUE 10: the old ``(num_partitions, sig)`` key collided two plans
  with equal device counts but different mesh shape or run option
  into one engine). The auto-searches (partition and mesh) replan by
  rebuilding the engine per candidate; before this cache the search
  then rebuilt — and re-jitted, and recompiled — the WINNING candidate
  a second time after it had already been measured
  (``session._record_search_time``). A cached engine keeps its jitted
  step's compiled-executable cache, so switching back to the winner is
  a dictionary lookup plus a state reshard, zero XLA work.

* JAX's persistent compilation cache (on-disk, cross-process):
  ``ensure_persistent_cache`` is the ONE place that decides where it
  lives, and every entry point (``ParallaxSession``, ``ServeSession``,
  ``chip_smoke.py``, ``benchmark/``, ``tests/conftest.py``) goes through
  it, so a relaunched job (same model, same toolchain) skips XLA
  entirely — compiles become disk reads. Keyed by HLO + compile
  environment, and the HLO's metadata (named scopes, source lines
  relative to the checkout) is part of the key: a stale cache can only
  miss, never corrupt — not the program and not the names that
  ``Engine.layer_index()`` reads off the executable.

What jax itself knows about a compile — how long it traced, lowered
and compiled, and whether the persistent cache served it — is heard by
``compile_events`` (one ``CompileEvents`` a process, listening from the
first ``ensure_persistent_cache`` on) and shown by every session's
registry under ``compile.*``.
"""

from __future__ import annotations

import functools
import os
import re
import threading
import time
from typing import Dict, Optional, Tuple

import jax

from parallax_tpu.common.lib import parallax_log
from parallax_tpu.obs import _state as obs_state
from parallax_tpu.obs import metrics as obs_metrics
from parallax_tpu.obs import trace

CHECKOUT_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
# <checkout>/.jax_cache (listed in .gitignore). A fixed path: the
# directory is part of how a cache is found again, so one made from
# tempfile, a pid or the time would never hit.
CHECKOUT_CACHE_DIR = os.path.join(CHECKOUT_ROOT, ".jax_cache")


# jax's three nested-able compile phases (``dispatch.log_elapsed_time``
# announces each one's start as a scalar and its end as a duration):
# event -> (registry name, span name)
_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration":
        ("compile.trace_s", "jax.trace"),
    "/jax/core/compile/jaxpr_to_mlir_module_duration":
        ("compile.lower_s", "jax.lower"),
    "/jax/core/compile/backend_compile_duration":
        ("compile.backend_s", "jax.backend_compile"),
}
# plain durations, parts of ``compile.backend_s``
_DURATIONS = {
    "/jax/compilation_cache/cache_retrieval_time_sec":
        "compile.cache_retrieval_s",
    "/jax/compilation_cache/compile_time_saved_sec":
        "compile.cache_saved_s",
}
_COUNTS = {
    "/jax/compilation_cache/cache_hits": "compile.cache_hits",
    "/jax/compilation_cache/cache_misses": "compile.cache_misses",
    "/jax/compilation_cache/compile_requests_use_cache":
        "compile.cache_requests",
}
# a trace or a lowering shorter than this leaves no span: one step's
# trace holds hundreds of inner jits of a millisecond each
_SPAN_MIN_S = 0.010


class CompileEvents:
    """jax's own compile events, summed over the process.

    ``jax.monitoring`` tells whoever listens how long every jitted
    function took to trace (``compile.trace_s``), to lower to MLIR,
    Pallas kernels to Mosaic included (``compile.lower_s``), and to
    compile or to be read back from the persistent cache
    (``compile.backend_s``: the event wraps ``compile_or_get_cached``;
    ``compile.cache_retrieval_s`` is the part of it spent reading and
    deserialising hits, ``compile.cache_saved_s`` what the hits say
    they saved), and counts the persistent cache's requests, hits and
    misses (a miss is counted where its entry is written).

    The three phases nest — an inner ``jit`` is traced inside its
    caller's trace, and an operation on concrete values inside a trace
    is dispatched, so traced, lowered and compiled, inside it — and
    each second is counted ONCE, under the innermost phase open on its
    thread: the three sums never exceed the wall clock they cover.

    A backend compile, and a trace or a lowering over 10 ms, also
    leaves a span (``jax.backend_compile``, ``jax.trace``,
    ``jax.lower``) carrying ``fun``, the function's name, so that the
    exported chrome trace says which function compiled when.

    The events are the process's, not a session's: ``expose`` hangs the
    sums into a registry as gauges that read them at snapshot time.
    Nothing is heard while ``obs`` is disabled.
    """

    NAMES = tuple(name for name, _ in _PHASES.values()) \
        + tuple(_DURATIONS.values()) + tuple(_COUNTS.values())

    def __init__(self):
        self._lock = threading.Lock()
        self._sums: Dict[str, float] = {
            name: 0 if name in _COUNTS.values() else 0.0
            for name in self.NAMES}
        # per thread: the seconds of finished phases inside each phase
        # that is still open
        self._open = threading.local()
        self._listening = False

    def listen(self) -> None:
        """Register with ``jax.monitoring``, once however often called."""
        with self._lock:
            if self._listening:
                return
            self._listening = True
        jax.monitoring.register_scalar_listener(self._on_start)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def value(self, name: str):
        with self._lock:
            return self._sums[name]

    def snapshot(self) -> Dict[str, float]:
        with self._lock:
            return dict(self._sums)

    def expose(self, registry: obs_metrics.MetricsRegistry) -> None:
        for name in self.NAMES:
            registry.gauge(name).set_fn(
                functools.partial(self.value, name))

    def _add(self, name: str, amount) -> None:
        with self._lock:
            self._sums[name] += amount

    def _on_start(self, event: str, value, **kwargs) -> None:
        if event in _PHASES:
            stack = getattr(self._open, "stack", None)
            if stack is None:
                stack = self._open.stack = []
            stack.append(0.0)

    def _on_duration(self, event: str, duration: float, **kwargs) -> None:
        phase = _PHASES.get(event)
        if phase is None:
            name = _DURATIONS.get(event)
            if name is not None and obs_state.enabled:
                self._add(name, duration)
            return
        # kept in step with _on_start whether or not obs is enabled
        stack = getattr(self._open, "stack", None)
        inside = stack.pop() if stack else 0.0
        if stack:
            stack[-1] += duration
        if not obs_state.enabled:
            return
        name, span_name = phase
        self._add(name, max(0.0, duration - inside))
        if span_name == "jax.backend_compile" or duration >= _SPAN_MIN_S:
            now = time.perf_counter()
            trace.record_span(span_name, now - duration, now,
                              fun=kwargs.get("fun_name"))

    def _on_event(self, event: str, **kwargs) -> None:
        name = _COUNTS.get(event)
        if name is not None and obs_state.enabled:
            self._add(name, 1)


compile_events = CompileEvents()


# the directory the first call settled on; decided once per process so
# a later session cannot switch the cache back on after the pipeline
# engines' guard (core/engine._guard_persistent_cache_for_pipeline)
# turned it off
_decided_dir: Optional[str] = None


def ensure_persistent_cache(explicit_dir: Optional[str] = None) -> str:
    """Turn JAX's persistent compilation cache on and return the
    directory it uses. Process-global (the cache is a backend
    property): the first call decides, later calls return its answer.

    * ``JAX_COMPILATION_CACHE_DIR`` set: the cache was placed from
      outside (a machine that keeps it between runs). jax reads the
      variable itself and this program sets NO directory in code —
      not from ``explicit_dir``, not a default.
    * unset: ``explicit_dir`` (a user's fixed path,
      ``Config.compilation_cache_dir``) when given, else
      ``CHECKOUT_CACHE_DIR``.

    Every executable is cached (threshold 0 s), so a second run of
    the same program adds no entries — jax's default threshold of 1 s
    would re-decide borderline compiles run by run. Like the
    directory, a threshold exported in the environment
    (``JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS``) is left alone.

    The key holds the HLO's metadata. By default jax strips it
    (``strip-debuginfo``), and a program that differs from a cached
    one only in a ``jax.named_scope`` or a kernel's name is then handed
    the OLD executable, whose ``as_text()`` carries the old names:
    everything that reads layers off the executable
    (``Engine.layer_index()``) would read the previous source's. With
    the metadata in, the names are this source's. Source paths lose
    everything up to the checkout's root first, so two checkouts of one
    commit still share entries. The price: an edit that moves a source
    line under a jitted function compiles it once more at the next
    start; a relaunch of unchanged code still hits.

    Every call also makes sure ``compile_events`` listens, so that the
    process's compiles are heard from its first entry point on.
    """
    global _decided_dir
    compile_events.listen()
    if _decided_dir is not None:
        if explicit_dir and explicit_dir != _decided_dir:
            parallax_log.warning(
                "compilation_cache_dir=%s ignored: this process's "
                "persistent compilation cache is already at %s",
                explicit_dir, _decided_dir)
        return _decided_dir
    if "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in os.environ:
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          0.0)
    jax.config.update("jax_compilation_cache_include_metadata_in_key",
                      True)
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      "^" + re.escape(CHECKOUT_ROOT + os.sep))
    _decided_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not _decided_dir:
        _decided_dir = explicit_dir or CHECKOUT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", _decided_dir)
    parallax_log.info("persistent compilation cache at %s",
                      _decided_dir)
    return _decided_dir


class EngineCache:
    """Built engines keyed by ``(plan..., batch-signature)``.

    The session keys with the BUCKETED example-batch signature
    (``ParallaxSession._bucketed_example``): ragged and full example
    batches of one bucket key identically, so a ragged tail landing
    right before the partition search settles cannot make the winner
    lookup miss. Without buckets declared the raw signature is the
    key. Hit/miss counts flow through the session's registry
    (``session.engine_cache.*``).
    """

    def __init__(self, metrics: Optional[obs_metrics.MetricsRegistry]
                 = None):
        registry = metrics if metrics is not None \
            else obs_metrics.MetricsRegistry()
        self._hits = registry.counter("session.engine_cache.hits")
        self._misses = registry.counter("session.engine_cache.misses")
        self._engines: Dict[Tuple, object] = {}

    def get(self, key: Tuple):
        eng = self._engines.get(key)
        if eng is not None:
            self._hits.inc()
        else:
            self._misses.inc()
        return eng

    def put(self, key: Tuple, engine) -> None:
        self._engines[key] = engine

    def prune(self, keep) -> int:
        """Drop every cached engine except ``keep`` (the search winner)
        and return how many were dropped. Dropped engines are NOT
        ``close()``d: close() restores process-global jax settings
        (``jax_debug_nans``) that the surviving engine still owns —
        the executables they hold are freed by GC."""
        dropped = [k for k, e in self._engines.items() if e is not keep]
        for k in dropped:
            del self._engines[k]
        return len(dropped)

    def engines(self):
        return list(self._engines.values())

    def __len__(self) -> int:
        return len(self._engines)
