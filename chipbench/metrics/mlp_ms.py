"""Device milliseconds per step of the model's FFNs (dense MLP or MoE),
forward, backward and remat recompute: the model bucket's ``model.mlp``
part (``chipbench.splits``). Nothing to read in a model without one."""


def read(ctx):
    return ctx["parts"]["model"].get("model.mlp")
