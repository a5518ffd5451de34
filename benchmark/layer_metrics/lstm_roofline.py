"""LSTM kernels: the two kernels' share of their roofline, %: the least
time the chip could take for their operations and bytes on this
device's share of the batch (``kernels/pallas_lstm.cost``), over their
time in the trace."""

from kernels import pallas_lstm, roofline


def read(ctx):
    ms = ctx.cell.plugin("layer_metrics",
                         "lstm_kernel_ms_per_step").read(ctx)
    if ms is None or ctx.peaks is None:
        return None
    model, mix = ctx.cell.model, ctx.cell.mix
    cost = pallas_lstm.cost(
        T=int(mix["num_steps"]),
        B=int(mix["global_batch"]) // ctx.cell.chips,
        E=int(model["emb_dim"]), H=int(model["hidden_dim"]),
        P=int(model["proj_dim"]))
    return roofline.share_percent(cost, ctx.peaks, ms * 1e-3)
