"""The traced sub-window: the profiler around a few seconds in the
middle of the measured window, two marks that put the program's spans
and the device's operations on one clock, and the window's spans.

Only the process that holds the chip can trace it, so this runs in the
benchmark's one process. End-to-end numbers are taken with it off."""

from __future__ import annotations

import glob
import os
import shutil
import time
from typing import List, Optional


def start_profiler(directory: str) -> float:
    """Start the profiler (the host's Python untraced) and write the
    ``bench.sync`` mark; returns the mark's ``perf_counter`` time."""
    import jax

    shutil.rmtree(directory, ignore_errors=True)    # keep the newest
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(directory, profiler_options=options)
    t_sync = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.sync"):
        pass
    return t_sync


def stop_profiler() -> float:
    """Write the ``bench.end`` mark and stop the profiler; returns the
    mark's ``perf_counter`` time."""
    import jax

    t_end = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.end"):
        pass
    jax.profiler.stop_trace()
    return t_end


def newest_xplane(directory: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(
        directory, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


class TraceWindow:
    """``poll(now)`` from the measuring loop starts the profiler at
    ``start_at`` seconds into the window and stops it ``seconds``
    later. Both calls run on the calling thread."""

    def __init__(self, out_dir: str, start_at: float, seconds: float):
        self.dir = os.path.join(out_dir, "profile")
        self.start_at = float(start_at)
        self.seconds = float(seconds)
        self.t_sync: Optional[float] = None     # perf_counter at bench.sync
        self.t_end: Optional[float] = None      # perf_counter at bench.end
        self.stop_seconds: Optional[float] = None
        self._t0: Optional[float] = None

    def begin_window(self, t0: float) -> None:
        self._t0 = t0

    @property
    def done(self) -> bool:
        return self.t_end is not None

    def poll(self, now: float) -> bool:
        """True when this call started or stopped the profiler."""
        if self.done or self._t0 is None:
            return False
        if self.t_sync is None:
            if now - self._t0 >= self.start_at:
                self._start()
                return True
        elif now - self.t_sync >= self.seconds:
            self._stop()
            return True
        return False

    def _start(self) -> None:
        from parallax_tpu.obs import trace as obs_trace

        # the program's span ring starts empty, so it holds the window
        obs_trace.get_collector().clear()
        self.t_sync = start_profiler(self.dir)
        # the same instant in the program's span ring: its clock's origin
        # is then read off this span, not off a private of the program
        obs_trace.record_span("bench.sync", self.t_sync, self.t_sync)

    def _stop(self) -> None:
        self.t_end = stop_profiler()
        self.stop_seconds = time.perf_counter() - self.t_end

    def finish(self) -> None:
        """Stop a trace that the window ended under."""
        if self.t_sync is not None and not self.done:
            self._stop()

    def xplane_path(self) -> Optional[str]:
        return newest_xplane(self.dir)


def spans_between(t_lo: float, t_hi: float) -> List[dict]:
    """The program's spans (``obs/trace``) that start inside
    ``[t_lo, t_hi]``, with ``perf_counter`` times. ``t_lo`` is the
    instant of the ``bench.sync`` span."""
    from parallax_tpu.obs import trace as obs_trace

    events = obs_trace.get_collector().events()
    sync = [ev for ev in events if ev.name == "bench.sync"]
    if not sync:
        return []
    epoch = t_lo - sync[0].ts
    out = []
    for ev in events:
        start = ev.ts + epoch
        if t_lo <= start <= t_hi:
            out.append({"name": ev.name, "start": start,
                        "end": start + ev.dur, "thread": ev.thread_name,
                        "args": ev.args or {}})
    return out
