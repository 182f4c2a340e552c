"""Device milliseconds per step of the model's backward pass, its remat
recompute included: the model bucket's ops whose instructions are mostly
under JAX's ``transpose(`` (``chipbench.splits``)."""


def read(ctx):
    ms = ctx["parts"]["bwd"]
    return ms if ms > 0 else None
