"""Compile layer: how often set-up traced the model's loss
(``engine.model_traces`` when set-up ended: ``Model.call_loss`` runs
only under a trace). One whole trace of forward and backward is the
floor; each further one is seconds of Python at a real model's size."""


def read(ctx):
    return ctx.run["registry_before"].get("engine.model_traces")
