"""Model FLOP/s utilization of the whole training step, in percent: the
forward and backward FLOPs per token that the model requires (no recompute,
``chipbench.flops``) times the traced window's tokens/s, over the chips'
published bf16 peak (``chipbench.peaks``)."""


def read(ctx):
    peak = ctx["chips"] * ctx["peaks"].bf16_flops
    return 100.0 * ctx["model_flops_per_token"] * ctx["tokens_per_s"] / peak
