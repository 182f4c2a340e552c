"""Device milliseconds per step in which a collective ran and no compute
op ran on that chip: the part of ``collective_ms`` that the step waits for,
averaged over the cell's chips. Nothing to read without collectives."""


def read(ctx):
    t = ctx["trace"]
    return t["collective_exposed_ms"] if t["has_collectives"] else None
