"""Profile one LM1B hybrid train step on the live backend.

Captures a jax.profiler trace of a few steady-state steps and
summarizes it through the shared ``obs/xprof`` parser (ONE owner for
trace parsing, ISSUE 13) so the hotspot is readable without
TensorBoard: top ops by self-duration with their taxonomy category,
the category split, and the coverage/residual account. Usage:

    python tools/profile_lm1b.py [outdir]
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

TRACED_STEPS = 8


def run_trace(outdir: str):
    """Returns the compiled step's HLO index (the session's
    ``layer_index()``) so the summary can join trace op names back to
    the layers' scopes — the layer / dense-sparse / fwd-bwd
    attribution rows."""
    import jax
    import numpy as np
    import parallax_tpu as parallax
    from parallax_tpu.models import lm1b

    n_chips = jax.device_count()
    platform = jax.devices()[0].platform
    mode = os.environ.get("PARALLAX_PROFILE_GRAD_MODE", "slices")
    # 'pallas' profiles the flagship's kernel-served recurrence
    # (ISSUE 14); default keeps the historical xla scan
    lstm_impl = os.environ.get("PARALLAX_PROFILE_LSTM_IMPL", "xla")
    if platform == "cpu":
        cfg = lm1b.tiny_config(num_partitions=n_chips,
                               sparse_grad_mode=mode,
                               lstm_impl=lstm_impl)
        bs, T = 16 * n_chips, 8
    else:
        cfg = lm1b.LM1BConfig(num_partitions=n_chips,
                              sparse_grad_mode=mode,
                              lstm_impl=lstm_impl)
        bs, T = 128 * n_chips, 20
    sess, *_ = parallax.parallel_run(
        lm1b.build_model(cfg),
        parallax_config=parallax.Config(run_option="HYBRID",
                                        search_partitions=False,
                                        sparse_grad_mode=mode))
    rng = np.random.default_rng(0)
    batches = [lm1b.make_batch(rng, bs, T, cfg.vocab_size)
               for _ in range(4)]
    # the AOT executable is what layer_index() reads its names off
    sess.warmup(feed_dict=batches[0], batch_sizes=[bs])
    for i in range(5):
        sess.run("loss", feed_dict=batches[i % 4])
    jax.block_until_ready(sess.state.params)
    with jax.profiler.trace(outdir):
        for i in range(TRACED_STEPS):
            sess.run("loss", feed_dict=batches[i % 4])
        jax.block_until_ready(sess.state.params)
    t0 = time.perf_counter()
    for i in range(10):
        sess.run("loss", feed_dict=batches[i % 4])
    jax.block_until_ready(sess.state.params)
    print(f"# step time (untraced): "
          f"{(time.perf_counter() - t0) / 10 * 1e3:.1f} ms "
          f"({platform}, bs={bs}, T={T}, lstm_impl={lstm_impl})")
    layer_index = sess.layer_index()
    sess.close()
    return layer_index and layer_index["hlo_index"]


def summarize(outdir: str, top: int = 25, hlo_index=None) -> None:
    """Shared-parser summary (obs/xprof): top ops by SELF duration
    (nesting resolved, unlike the old inline aggregation that counted
    a while loop and its body twice), the category split, the
    coverage/residual account, and — with an ``hlo_index`` — the
    forward/backward attribution row (ISSUE 14: where the training
    step's backward actually goes) plus the per-op LSTM rows."""
    from parallax_tpu.obs import xprof

    try:
        trace, path = xprof.load_trace(outdir)
    except FileNotFoundError:
        print("no trace.json(.gz) found under", outdir)
        return
    attrib = xprof.attribute(trace, steps=TRACED_STEPS, top=top,
                             hlo_index=hlo_index, source=path)
    print(f"# {attrib.events} device op event(s) on {attrib.tracks} "
          f"track(s) [{attrib.track_basis}]")
    if attrib.coverage is not None:
        print(f"# device step wall {attrib.wall_ms:.2f} ms, "
              f"attributed {attrib.attributed_ms:.2f} ms "
              f"({attrib.coverage * 100:.1f}%), residual "
              f"{attrib.residual_ms:.2f} ms")
    for cat, row in attrib.by_category.items():
        print(f"# {cat:<11} {row['self_ms']:9.2f} ms  "
              f"share {row['share']:.3f}  x{row['events']}")
    # backward-attribution row (ISSUE 14): fwd-vs-bwd self-time from
    # the HLO op_name transpose(...) scopes; all-unmapped when no
    # hlo_index was joinable (visible, never fabricated)
    fb = attrib.fwd_bwd or {}
    total = sum(fb.values()) or 1.0
    print("# fwd/bwd     "
          + "  ".join(f"{k.replace('_self_ms', '')} "
                      f"{v:.2f} ms ({v / total:.0%})"
                      for k, v in fb.items()))
    # own time by layer (obs/xprof.LAYER_SCOPES; "(unmapped)" is the
    # glue under no layer's scope)
    for layer, v in attrib.layers.items():
        print(f"# layer       {layer:<40} {v:9.2f} ms")
    width = max((len(r["op"]) for r in attrib.top_ops), default=10)
    for r in attrib.top_ops:
        print(f"{r['op'][:90]:<{min(width, 90)}}  "
              f"{r['self_ms']:9.2f} ms  x{r['count']:<5} "
              f"[{r['category']}]")


if __name__ == "__main__":
    outdir = sys.argv[1] if len(sys.argv) > 1 else "/tmp/lm1b_profile"
    index = run_trace(outdir)
    summarize(outdir, hlo_index=index)
