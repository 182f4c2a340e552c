"""Device milliseconds per step of the compressor's orthonormalisation of
P: the compressor bucket's ``lowrank.orth`` part (``chipbench.splits``)."""


def read(ctx):
    return ctx["parts"]["compress"].get("lowrank.orth")
