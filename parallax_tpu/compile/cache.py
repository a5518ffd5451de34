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
"""

from __future__ import annotations

import os
import re
from typing import Dict, Optional, Tuple

import jax

from parallax_tpu.common.lib import parallax_log
from parallax_tpu.obs import metrics as obs_metrics

CHECKOUT_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
# <checkout>/.jax_cache (listed in .gitignore). A fixed path: the
# directory is part of how a cache is found again, so one made from
# tempfile, a pid or the time would never hit.
CHECKOUT_CACHE_DIR = os.path.join(CHECKOUT_ROOT, ".jax_cache")


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
    """
    global _decided_dir
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
