"""Share of the traced window in which no op ran on the device: 1 minus the
union of the innermost device-op intervals over the window, averaged over
the cell's chips."""


def read(ctx):
    return ctx["trace"]["idle_pct"]
