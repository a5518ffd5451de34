"""Experts: the fullest held expert's rows over the held experts' mean,
mean over the layers, at the last step of the window (the step output
``moe_load_max_over_mean``, polled into the session's registry as the
gauge ``moe.load_max_over_mean``). 1 is perfect balance; the grouped
products' tail and the deployment's straggler grow with it."""


def read(ctx):
    return ctx.run["registry_after"].get("moe.load_max_over_mean")
