"""Device milliseconds per step of the non-collective ops that do any of the
compressor's work (an instruction under a ``comp.*``, ``lazy.*`` or
``wire.*`` scope, fused or not)."""


def read(ctx):
    ms = ctx["trace"]["compress_ms"]
    return ms if ms > 0 else None
