"""The device's time by layer: each traced operation's own time, summed
under the layer whose ``jax.named_scope`` emitted its instruction.

The device trace cannot carry the layers' names: an event of the
operations' line is named by the HLO instruction's text without its
metadata. The compiled program can: every instruction of
``compiled.as_text()`` has an ``op_name`` that holds the scopes it was
traced under, and the program hands that join over
(``session.layer_index()``: ``{"module": the step program's name,
"layers": {instruction name: layer or None}, ...}``, the layers being
``parallax_tpu/obs/xprof.LAYER_SCOPES``). The account joins the two by
instruction name, over the runs of that one program on the first
device.

Own times (``reduce/xplane.self_times``: a ``while`` holds its body and
keeps only what the body does not cover) of one serial stream add up to
its busy time, so the layers, ``UNSCOPED`` and ``UNKNOWN`` together are
``device.busy_s`` of a one-chip cell.
"""

from __future__ import annotations

import bisect
import collections
from typing import Dict, List, Optional

from reduce import xplane

# instructions the index holds under no layer's scope: the model's own
# glue (dropout's random bits, transposes, the loss's mean) and the
# compiler's copies
UNSCOPED = "unscoped"
# events whose instruction the index does not hold, or that ran outside
# the indexed program: index and trace are then not of one executable.
# Must read 0.
UNKNOWN = "unknown"


def own_seconds_by_layer(ops: List[xplane.Op], lo: float, hi: float,
                         index: dict,
                         runs: Optional[List[xplane.Op]] = None
                         ) -> Dict[str, float]:
    """``{layer | UNSCOPED | UNKNOWN: own seconds inside [lo, hi]}`` of
    one device's operations (sorted by start, parents first). ``runs``
    are that device's program runs: where given, only the operations
    that start inside a run of ``index["module"]`` are joined by name,
    since another program's ``fusion.3`` is not this one's. Every layer
    of ``index["scopes_found"]`` has a row, so a layer that took no
    time reads 0 and a layer the program lacks reads nothing."""
    layers = index["layers"]
    if runs is None:
        inside, outside = ops, []
    else:
        mine = sorted((r.start, r.end) for r in runs
                      if r.name == index["module"])
        starts = [s for s, _ in mine]
        inside, outside = [], []
        for op in ops:
            i = bisect.bisect_right(starts, op.start) - 1
            (inside if i >= 0 and op.start < mine[i][1]
             else outside).append(op)
    out: Dict[str, float] = collections.defaultdict(float)
    for layer in index["scopes_found"]:
        out[layer] = 0.0
    for event_name, seconds in xplane.self_times(inside, lo, hi).items():
        name = xplane.parse_instruction(event_name)[0]
        out[(layers[name] or UNSCOPED) if name in layers
            else UNKNOWN] += seconds
    out[UNKNOWN] += sum(xplane.self_times(outside, lo, hi).values())
    out.setdefault(UNSCOPED, 0.0)
    return dict(out)


def account(ctx) -> Optional[Dict[str, float]]:
    """The traced window's account on the first device, computed once a
    context. None where the program has no ``layer_index()`` (a commit
    before it), no AOT executable, or the trace no device plane (the
    CPU rehearsal)."""
    if not hasattr(ctx, "layer_account"):
        ctx.layer_account = _account(ctx)
    return ctx.layer_account


def _account(ctx) -> Optional[Dict[str, float]]:
    devs = ctx.device_ops()
    layer_index = getattr(ctx.run["system"].session, "layer_index", None)
    index = layer_index() if layer_index is not None else None
    if not devs or not index:
        return None
    ordinal, ops = devs[0]
    lo, hi = ctx.window
    return own_seconds_by_layer(ops, lo, hi, index,
                                ctx.trace.modules.get(ordinal) or None)


def layer_ms_per_step(ctx, *rows: str) -> Optional[float]:
    """Milliseconds a step of the account's ``rows`` together; None
    without an account or where it has none of them."""
    acc = account(ctx)
    if acc is None or not any(r in acc for r in rows):
        return None
    return ctx.per_step_ms(sum(acc.get(r, 0.0) for r in rows),
                           "train_step", "engine.step")


def coverage_percent(ctx) -> Optional[float]:
    """The declared layers' own time over the device's busy time, %."""
    acc = account(ctx)
    if acc is None:
        return None
    busy = sum(acc.values())
    if busy <= 0:
        return None
    return 100.0 * (busy - acc[UNSCOPED] - acc[UNKNOWN]) / busy
