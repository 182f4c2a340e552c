"""Device milliseconds per step in which a collective ran (synchronous
collective ops, and asynchronous ones from start to done), averaged over
the cell's chips. Nothing to read where the step has no collective."""


def read(ctx):
    t = ctx["trace"]
    return t["collective_ms"] if t["has_collectives"] else None
