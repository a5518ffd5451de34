"""Attention, the indexer: device time of one step in the backward
kernel of the indexer's scores (``ops/sparse_attention``'s
``indexer_bwd``, one Mosaic call a query chunk a layer): the own time
of the operations named ``indexer_bwd.N`` that the program's
``session.layer_index()`` holds under ``indexer``, first device. A part
of ``indexer_ms_per_step``. A program without the kernel (a commit
before PR 28) reads nothing.

Not ``lib/kernel_calls``: that counts operations of category ``mosaic``
(the opcode ``custom-call``), and in the Keye step the compiler wraps
this call in a fusion of kind ``kCustom`` that keeps the call's name and
writes its result straight into the chunk loop's stacked output (PERF.md,
PR 28). The name is the ``pallas_call``'s own, so both forms count."""

from reduce import xplane

PREFIX, LAYER = "indexer_bwd", "indexer"


def calls(ctx):
    """``(own seconds a step, calls a step)`` of the kernel, or None
    where the trace, the index or the kernel is missing."""
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
        name, opcode, _ = xplane.parse_instruction(op.name)
        return opcode in ("custom-call", "fusion") \
            and name.split(".")[0] == PREFIX and layers.get(name) == LAYER

    found = [op for op in ops if lo <= op.start < hi and mine(op)]
    if not found:
        return None
    own = xplane.self_times(ops, lo, hi)
    seconds = sum(own.get(name, 0.0) for name in {op.name for op in found})
    return seconds / steps, len(found) / steps


def read(ctx):
    found = calls(ctx)
    return None if found is None else 1e3 * found[0]
