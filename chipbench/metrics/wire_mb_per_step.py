"""Megabytes each worker puts on the interconnect per step: the training
step's own ``wire_mb_per_step`` count (codes, scales and raw leaves as the
wire codec sends them), as the last window step reported it."""


def read(ctx):
    mb = ctx["wire_mb_per_step"]
    return mb if mb and mb > 0 else None
