"""Linear attention: the mean over heads, tokens and linear layers of
``alpha = exp(g)``, the state's decay a token, at the last step of the
window (the step output ``linear_decay_mean``, polled into the session's
registry as the gauge ``linear_attn.decay_mean``). A gauge of the
layer's health, not a lever on the rate: near 0 the state forgets
everything and the layer is a convolution of four taps, near 1 it
forgets nothing. ``BENCHMARK.json``'s form asks every metric for a
``better`` and a ``moves``; this one's name the cell's rate because the
form has no "neither" (``router_gate_mean``'s way)."""


def read(ctx):
    return ctx.run["registry_after"].get("linear_attn.decay_mean")
