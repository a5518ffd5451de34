"""Router: the mean over tokens and layers of the chosen expert's
probability, the top-1 gate as it multiplies the expert's output, at
the last step of the window (the step output ``router_gate_mean``,
polled into the session's registry as the gauge ``router.gate_mean``).
A gauge of the router's health, not a lever on the rate: one over the
number of experts says the router has stopped choosing; 1 that it no
longer learns from its gate. ``BENCHMARK.json``'s form asks every
metric for a ``better`` and a ``moves``; this one's say "away from
1 / 16" and name the cell's rate because the form has no "neither"."""


def read(ctx):
    return ctx.run["registry_after"].get("router.gate_mean")
