"""AOT warmup: compile every declared bucket ahead of step 0.

The engine already knows how to lower its step for export
(``Engine._export_graph``); warmup runs the same ``lower()`` through
``compile()`` for each declared batch-shape bucket BEFORE the first
step, so step 0 — and the first ragged tail, and every other bucket —
dispatches a ready executable instead of stalling the loop on a full
XLA compile. The resulting executables are held by the engine and
dispatched by shape signature (``Engine.step``); per-signature compile
wall-time lands in the ``engine.compile_seconds`` histogram and in
``Engine.warmup_seconds`` (both reported by
``ParallaxSession.compile_stats``). Inside the ``engine.warmup_compile``
span the two halves have spans of their own: ``engine.lower`` (the
step traced and lowered, in Python) and ``engine.compile`` (XLA's
compile, or the persistent cache's entry read back: ``cache_hit``).

Lowering needs concrete input layouts: the live ``TrainState`` carries
its real shardings, and batch avals are ``ShapeDtypeStruct``s with the
same ``NamedSharding`` placement ``shard_batch`` will use — so the
compiled executable accepts the session's real step inputs exactly.
"""

from __future__ import annotations

import time
from typing import Dict, Optional, Sequence

from parallax_tpu.common.lib import parallax_log
from parallax_tpu.compile import bucketing
from parallax_tpu.compile.cache import compile_events
from parallax_tpu.obs import trace


def aot_warmup(engine, state, batch_sizes: Optional[Sequence[int]] = None
               ) -> Dict[int, float]:
    """Compile the step for each bucket size; returns {size: seconds}.

    ``batch_sizes`` defaults to the engine's declared buckets
    (``Config.shape_buckets``). Sizes already compiled are skipped, so
    warmup is idempotent and incremental. The compiled signature is
    registered as expected, so warmed buckets never count into
    ``engine.recompiles``.
    """
    sizes = batch_sizes if batch_sizes is not None else engine._buckets
    if not sizes:
        raise ValueError(
            "warmup has no signatures to compile: declare "
            "Config.shape_buckets (or 'auto'), or pass explicit batch "
            "sizes")
    stats: Dict[int, float] = {}
    for b in sizes:
        b = int(b)
        avals = engine._bucket_avals(b)
        sig = bucketing.batch_signature(avals)
        if sig in engine._executables:
            continue
        t0 = time.perf_counter()
        with trace.span("engine.warmup_compile", batch=b):
            with trace.span("engine.lower", batch=b):
                lowered = engine._step_jit.lower(state, avals)
            t1 = time.perf_counter()
            hits = compile_events.value("compile.cache_hits")
            compiled = lowered.compile()
            t2 = time.perf_counter()
            # (False too while obs is disabled: nobody counted the hit)
            cache_hit = compile_events.value("compile.cache_hits") > hits
            trace.record_span("engine.compile", t1, t2, batch=b,
                              cache_hit=cache_hit)
        dt = time.perf_counter() - t0
        engine._executables[sig] = compiled
        engine._traced_signatures.add(sig)
        engine.metrics.histogram("engine.compile_seconds").record(dt)
        stats[b] = dt
        parallax_log.info(
            "warmup: step for batch bucket %d %s in %.2fs (traced and "
            "lowered in %.2fs, %s in %.2fs)", b,
            "loaded from the cache" if cache_hit else "compiled", dt,
            t1 - t0, "loaded" if cache_hit else "compiled", t2 - t1)
    engine.warmup_seconds.update(stats)
    return stats
