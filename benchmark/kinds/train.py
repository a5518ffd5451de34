"""One run of a training cell: build, warm up, dispatch steps for the
window, block on the state, check.

The builder hands over the program's own session; the loop is the one a
user writes: ``for loss in sess.run_iter(batches)`` with the session's
prefetch running, reading each loss a few steps behind so that the host
never runs further ahead of the device than ``max_inflight_steps``.

The rate is the median over ``GROUPS`` consecutive parts of the window,
each timed from the completion of one step to the completion of
another. On the one-chip machine the host's cores are shared, and a run
can lose a tenth of a second, or three, to a stall that is none of the
program's work (PERF.md, PR 23: 11 of 24 runs lost ~95 ms once, one
lost 3.3 s); steps over the whole window's seconds would carry every
such stall into the result. A slowdown of the steps themselves, or
stalls in more than half of the parts, still moves the median. The
whole window's rate is on the ``benchmark-detail`` line.
"""

from __future__ import annotations

import collections
import math
import threading
import time

from lib import stats

GROUPS = 40


def run(cell, args, clock_start: float, trace_window, log):
    import jax

    builder = cell.plugin("builders", cell.config["builder"])
    generator = cell.plugin("generators", cell.traffic["generator"])
    mix = cell.mix

    system = builder.build(cell, seed=args.seed)
    sess = system.session
    feeds = generator.make(mix, seed=args.seed,
                           vocab_size=system.vocab_size)
    tokens_per_step = generator.tokens_per_step(mix)

    t = time.perf_counter()
    sess.warmup(feed_dict=feeds[0])
    log(f"warmup (compile or cache load) {time.perf_counter() - t:.1f}s")
    warm_losses = [float(sess.run("loss", feed_dict=feeds[i % len(feeds)]))
                   for i in range(int(mix.get("warm_steps", 2)))]
    jax.block_until_ready(sess.state.params)
    static_failures = system.static_checks()
    before = sess.metrics_snapshot()

    stop = threading.Event()

    def stream():
        i = 0
        while not stop.is_set():
            yield feeds[i % len(feeds)]
            i += 1

    max_inflight = int(mix.get("max_inflight_steps", 4))
    inflight = collections.deque()
    losses = []
    done_at = []        # when each loss was read: its step had completed
    dispatched = 0
    seconds = float(args.seconds)

    t0 = time.perf_counter()        # set-up ends, the window starts
    if trace_window is not None:
        trace_window.begin_window(t0)
    for loss in sess.run_iter(stream(), fetches="loss"):
        dispatched += 1
        inflight.append(loss)
        if len(inflight) > max_inflight:
            losses.append(float(inflight.popleft()))
            done_at.append(time.perf_counter())
        now = time.perf_counter()
        if trace_window is not None and trace_window.poll(now):
            log(f"trace edge at step {dispatched}, "
                f"{time.perf_counter() - t0:.2f}s into the window")
        if now - t0 >= seconds:
            stop.set()
            break
    jax.block_until_ready(sess.state.params)
    t1 = time.perf_counter()        # the window ends on the device
    if trace_window is not None:
        trace_window.finish()
    losses += [float(x) for x in inflight]
    window_s = t1 - t0
    after = sess.metrics_snapshot()

    failed = sum(1 for x in losses if not math.isfinite(x))
    recompiles = int(after.get("engine.recompiles", 0)) \
        - int(before.get("engine.recompiles", 0))
    n = len(feeds)
    fell = None
    if len(losses) >= 2 * n:
        first = sum(losses[:n]) / n
        last = sum(losses[-n:]) / n
        fell = bool(last < first)
    reference = system.reference_check(seed=args.seed)
    checks = {
        "static_failures": static_failures,
        "nonfinite_losses": failed,
        "recompiles_in_window": recompiles,
        "loss_fell_first_to_last_cycle": fell,
        "losses_first_last": [losses[0], losses[-1]] if losses else None,
        "warm_losses": warm_losses,
        "reference": reference,
    }
    correct = (not static_failures and failed == 0 and recompiles == 0
               and fell is True and bool(reference["ok"]))
    per_chip = tokens_per_step / cell.chips
    groups = stats.group_rates(done_at, seconds / GROUPS)
    steps_per_s = stats.median(groups)      # None without two completions
    whole_window = {
        "tokens_per_s_per_chip": dispatched * per_chip / window_s,
        "groups": len(groups),
        "slowest_group_over_median":
            min(groups) / steps_per_s if groups else None}
    result = {
        "correct": correct, "attempted": dispatched, "failed": failed,
        "end_to_end": {"train_tokens_per_s_per_chip":
                       steps_per_s * per_chip if groups else None},
        "checks": checks,
        "whole_window": whole_window,   # times: left out of a rehearsal
        # what the per-layer readers may look at
        "context": {
            "window_s": window_s, "tokens_per_step": tokens_per_step,
            "registry_before": before, "registry_after": after,
            "system": system,
        },
        "setup_s": t0 - clock_start,
    }
    sess.close()
    return result
