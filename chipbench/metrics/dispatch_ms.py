"""Milliseconds per window step that the runtime's main thread spent in
the call of the jitted step, which enqueues it on the device: the
program's ``runtime.dispatch`` span, as ``AsyncRunner.span_s`` sums it."""


def read(ctx):
    return ctx["span_ms"].get("runtime.dispatch")
