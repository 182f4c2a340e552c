"""Device milliseconds per step of the embedding, final norm, LM head and
cross-entropy, forward and backward: the model bucket's ``model.head``
part (``chipbench.splits``)."""


def read(ctx):
    return ctx["parts"]["model"].get("model.head")
