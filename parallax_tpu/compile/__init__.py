"""parallax_tpu.compile — the compile-ahead engine (ISSUE 3).

Parallax's promise is transparent speed on an unmodified single-device
program, but each new batch-shape signature costs a full XLA recompile
of the step: the final partial batch of an epoch retraces everything
(the ``engine.recompiles`` counter from the obs layer exists precisely
to flag this), and the partition search used to rebuild — and therefore
recompile — the winning engine a second time after it had already been
measured. Three cooperating parts drive those compiles to the minimum:

  * :mod:`~parallax_tpu.compile.bucketing` — batch-shape bucketing:
    ``Config(shape_buckets=[...])`` (or ``"auto"``) pads ragged batches
    up to a small declared set of bucket sizes with a per-example
    weight mask zeroed over the padded tail (``bucket_batch``, also
    exported as ``parallax_tpu.data.bucket_batch``), so a ragged stream
    presents a bounded set of shape signatures — each compiled once.
  * :mod:`~parallax_tpu.compile.warmup` — AOT warmup:
    ``Engine.warmup()`` / ``ParallaxSession.warmup()`` run
    ``jit.lower().compile()`` for every declared bucket ahead of step
    0 (optionally on a background thread overlapping data-pipeline
    startup), with per-signature compile wall-time recorded into the
    ``engine.compile_seconds`` histogram.
  * :mod:`~parallax_tpu.compile.cache` — executable/engine caching: the
    session keeps built engines keyed by ``(num_partitions,
    batch-signature)`` so the partition search reuses the measured
    winner instead of rebuilding it, and ``ensure_persistent_cache``
    places JAX's persistent compilation cache (where
    ``JAX_COMPILATION_CACHE_DIR`` says, else a fixed path) so repeated
    launches skip XLA entirely.

Everything reports through the obs layer: ``engine.compile_seconds``
(histogram), ``engine.executable_cache.{hits,misses}`` and
``session.engine_cache.{hits,misses}`` (counters), all carried by
``registry.snapshot()`` and reported whole by
``ParallaxSession.compile_stats()``.
"""

from parallax_tpu.compile.bucketing import (batch_signature, bucket_batch,
                                            resolve_buckets)
from parallax_tpu.compile.cache import (EngineCache,
                                        ensure_persistent_cache)
from parallax_tpu.compile.warmup import aot_warmup

__all__ = [
    "batch_signature", "bucket_batch", "resolve_buckets",
    "EngineCache", "ensure_persistent_cache", "aot_warmup",
]
