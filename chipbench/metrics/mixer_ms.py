"""Device milliseconds per step of the model's token mixers (SSD,
attention or MLA), forward, backward and remat recompute: the model
bucket's ``model.mixer`` part (``chipbench.splits``), with the tags
nested in it."""


def read(ctx):
    return ctx["parts"]["model"].get("model.mixer")
