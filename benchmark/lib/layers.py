"""What a per-layer reader sees, and the reduction of one traced run to
``device.busy_s``, ``device.window_s`` and ``breakdown``.

A reader is ``benchmark/layer_metrics/<metric>.py`` with one function,
``read(ctx) -> number or None``. It takes its metric from the program's
spans (``ctx.spans``), its counters (``ctx.run["registry_after"]`` and
the other keys the kind's loop left in ``ctx.run``), or the device
trace (``ctx.trace``, reduced by ``reduce/xplane.py``). A reader that
finds nothing to read returns None and the metric is left out.
"""

from __future__ import annotations

from typing import List, Optional

from lib import stats
from reduce import xplane


class Context:
    def __init__(self, cell, run: dict, device: dict, peaks: Optional[dict],
                 trace_window, trace, spans: List[dict]):
        self.cell = cell
        self.run = run              # what the kind's loop measured
        self.device = device        # platform, kind, count
        self.peaks = peaks          # the chip's row of peaks.json
        self.trace = trace          # xplane.Trace or None
        self.spans = spans          # program spans of the traced window
        # the traced window on the host's perf_counter clock ...
        self.t_lo = trace_window.t_sync if trace_window else None
        self.t_hi = trace_window.t_end if trace_window else None
        # ... and on the trace's clock
        self.window = xplane.window(trace) if trace else None
        self.offset = (self.t_lo - self.window[0]) if self.window else None

    # -- spans -------------------------------------------------------------

    def spans_named(self, *names: str) -> List[dict]:
        return [s for s in self.spans if s["name"] in names]

    @property
    def traced_seconds(self) -> Optional[float]:
        if self.t_lo is None or self.t_hi is None:
            return None
        return self.t_hi - self.t_lo

    def steps_in_trace(self, module_part: str, span_name: str) -> int:
        """Steps of the traced window: the runs of the step's program
        on the first device, or, where the trace has no line of program
        runs, the program's step spans on the host."""
        return len(self.module_runs(module_part)) \
            or len(self.spans_named(span_name))

    def per_step_ms(self, seconds: Optional[float], module_part: str,
                    span_name: str) -> Optional[float]:
        steps = self.steps_in_trace(module_part, span_name)
        if seconds is None or not steps:
            return None
        return 1e3 * seconds / steps

    # -- device ------------------------------------------------------------

    def device_ops(self):
        """``[(ordinal, ops)]`` for the devices of the trace."""
        if self.trace is None or self.window is None:
            return []
        return sorted(self.trace.devices.items())

    def async_ops(self, ordinal: int):
        """The device's asynchronous operations, each from its start to
        its done."""
        return self.trace.asyncs.get(ordinal, [])

    def mean_over_devices(self, fn) -> Optional[float]:
        """``fn(ops, lo, hi)`` averaged over the devices."""
        devs = self.device_ops()
        if not devs:
            return None
        lo, hi = self.window
        return sum(fn(ops, lo, hi) for _, ops in devs) / len(devs)

    def module_runs(self, name_part: str) -> List[float]:
        """Seconds of each run, inside the traced window, of the
        programs whose name holds ``name_part`` (first device)."""
        if self.trace is None or self.window is None \
                or not self.trace.modules:
            return []
        first = sorted(self.trace.modules.items())[0][1]
        return [r.end - r.start
                for r in xplane.runs_of(first, name_part, *self.window)]


def device_block(ctx: Context) -> dict:
    """``busy_s`` (averaged over the chips) and ``window_s``."""
    if not ctx.device_ops():
        return {}
    lo, hi = ctx.window
    busy = ctx.mean_over_devices(
        lambda ops, lo, hi: stats.total(xplane.busy(ops, lo, hi)))
    return {"busy_s": busy, "window_s": hi - lo}


HOST_SPANS = ("serve.prefill", "serve.prefill_chunk", "serve.step",
              "engine.step", "session.data_wait", "session.dispatch")


def breakdown(ctx: Context, top: int = 10) -> Optional[dict]:
    """The device operations with most own time (first device, under
    the names the trace prints), and the longest idle gaps of that
    device by the program span that was open on the host."""
    devs = ctx.device_ops()
    if not devs:
        return None
    lo, hi = ctx.window
    ops = devs[0][1]
    own = xplane.self_times(ops, lo, hi)
    device_ops = [[xplane.short_name(name), seconds] for name, seconds in
                  sorted(own.items(), key=lambda kv: -kv[1])[:top]]
    host = sorted((s for s in ctx.spans if s["name"] in HOST_SPANS),
                  key=lambda s: s["start"])
    gaps = []
    for a, b in xplane.idle_gaps(xplane.busy(ops, lo, hi), lo, hi)[:top]:
        mid = ctx.offset + 0.5 * (a + b)
        # the innermost program span open at the middle of the gap
        cover = [s for s in host if s["start"] <= mid <= s["end"]]
        name = min(cover, key=lambda s: s["end"] - s["start"])["name"] \
            if cover else "unattributed"
        gaps.append([name, b - a])
    return {"device_ops": device_ops, "idle_gaps": gaps}
