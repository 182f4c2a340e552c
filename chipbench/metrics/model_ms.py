"""Device milliseconds per step of the ops outside the compressor's
(``comp.*``, ``lazy.*``, ``wire.*``) and the metrics' (``train.metrics``)
scopes and outside collectives: the model's forward and backward, and the
optimizer's update where XLA did not fuse it into the compressor's passes."""


def read(ctx):
    return ctx["trace"]["model_ms"]
