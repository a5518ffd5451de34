"""The Mosaic calls of one kernel in the traced window: the operations
of category ``mosaic`` whose instruction the program's
``session.layer_index()`` holds under a given layer and whose name is
``<prefix>.N`` (a ``pallas_call``'s ``name``), first device. Never
"every ``tpu_custom_call``": a step has several kernels."""

from __future__ import annotations

from typing import Optional, Tuple

from reduce import xplane


def per_step(ctx, layer: str, prefix: str) -> Optional[Tuple[float, float]]:
    """``(own seconds a step, calls a step)``, or None where the trace,
    the index or the kernel is missing."""
    devs = ctx.device_ops()
    layer_index = getattr(ctx.run["system"].session, "layer_index", None)
    index = layer_index() if layer_index is not None else None
    steps = ctx.steps_in_trace("train_step", "engine.step")
    if not devs or not index or not steps:
        return None
    _, ops = devs[0]
    layers = index["layers"]
    lo, hi = ctx.window

    def mine(op):
        name = xplane.parse_instruction(op.name)[0]
        return layers.get(name) == layer and name.split(".")[0] == prefix

    calls = [op for op in ops if op.category == "mosaic"
             and lo <= op.start < hi and mine(op)]
    if not calls:
        return None
    own = xplane.self_times(ops, lo, hi)
    seconds = sum(own.get(name, 0.0) for name in {op.name for op in calls})
    return seconds / steps, len(calls) / steps
