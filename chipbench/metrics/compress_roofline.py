"""The compressor as one kernel, against its roofline, in percent: the least
time one rank-r LQ-SGD sync could take on this chip, the larger of its
bytes over the HBM peak and its FLOPs over the bf16 peak (both counted from
the leaf shapes by ``chipbench.flops.compress_cost``), over ``compress_ms``.
At the benchmark's ranks the bytes bound it."""


def read(ctx):
    ms = ctx["trace"]["compress_ms"]
    if ms <= 0:
        return None
    p = ctx["peaks"]
    least_s = max(
        ctx["compress_bytes"] / p.hbm_bytes_s, ctx["compress_flops"] / p.bf16_flops
    )
    return 100.0 * least_s * 1e3 / ms
