"""Router: the expert layers' mean of ``max - min`` of the balancing
biases ``b`` a layer, as the window's last step left them (the step
output ``router_bias_spread``, polled into the session's registry as the
gauge ``router.bias_spread``). A gauge of the routing's health, not a
lever on the rate: 0 says the rule never ran; a value that grows all
window long (the window's steps times ``load_balance_coeff`` and a
little more) says the routing is not at rest, for an expert that stays
on one side of its layer's mean moves a step's worth every step.
``BENCHMARK.json``'s form asks every metric for a ``better`` and a
``moves``; this one's name the cell's rate because the form has no
"neither" (``router_gate_mean``'s way). A program without the gauge
reads nothing."""


def read(ctx):
    return ctx.run["registry_after"].get("router.bias_spread")
