"""Milliseconds per window step that the runtime's main thread waited for
the prefetch thread's next batch: the program's ``runtime.prefetch_wait``
span, as ``AsyncRunner.span_s`` sums it."""


def read(ctx):
    return ctx["span_ms"].get("runtime.prefetch_wait")
