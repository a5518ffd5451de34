"""Step layer: model FLOP/s utilization, %: the matrix-product
operations the forward and backward passes need for a token (from the
configuration's reference file), times the tokens a chip completes a
second at the traced window's step time (``train_step_ms_p50``: the
whole window's rate holds the profiler's stop), over the chip's
published bf16 peak."""


def read(ctx):
    step_ms = ctx.cell.plugin("layer_metrics",
                              "train_step_ms_p50").read(ctx)
    if ctx.peaks is None or not step_ms:
        return None
    reference = ctx.cell.plugin("reference", ctx.cell.config_name)
    flops = reference.train_matmul_flops_per_token(ctx.cell.model)
    tokens_per_s = ctx.run["tokens_per_step"] / ctx.cell.chips \
        / (step_ms * 1e-3)
    return 100.0 * flops * tokens_per_s / ctx.peaks["bf16_flops_per_s"]
