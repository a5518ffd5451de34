"""Sparse path: device time of one step in the in-place row-update
kernels of ``ops/sparse_optim`` (``adagrad_rows``, one Mosaic call a
table the kernel serves): the own time of the trace's ``mosaic``
operations whose instruction the program's ``session.layer_index()``
holds under ``table_update``, first device. A part of
``table_update_ms_per_step``; the rest of that layer is the combining
of a step's duplicate rows. A program without such a kernel (a commit
before PR 26, a table the kernel does not take) reads nothing."""

from reduce import xplane


def read(ctx):
    devs = ctx.device_ops()
    layer_index = getattr(ctx.run["system"].session, "layer_index", None)
    index = layer_index() if layer_index is not None else None
    if not devs or not index:
        return None
    _, ops = devs[0]
    layers = index["layers"]
    kernels = {op.name for op in ops if op.category == "mosaic"
               and layers.get(xplane.parse_instruction(op.name)[0])
               == "table_update"}
    if not kernels:
        return None
    own = xplane.self_times(ops, *ctx.window)
    return ctx.per_step_ms(sum(own.get(k, 0.0) for k in kernels),
                           "train_step", "engine.step")
