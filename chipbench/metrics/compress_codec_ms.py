"""Device milliseconds per step of the wire codec, collectives left out:
scale, quantise and pack (``codec.encode``), unpack, dequantise and
average (``codec.decode``), the compressor bucket's ``codec.*`` parts
(``chipbench.splits``)."""


def read(ctx):
    parts = ctx["parts"]["compress"]
    codec = [ms for k, ms in parts.items() if k.startswith("codec.") and "/" not in k]
    return sum(codec) if codec else None
