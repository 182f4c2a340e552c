"""Milliseconds per window step that the runtime's dispatch thread spent
blocked on the prefetch queue or on fetching metrics (``AsyncRunner.host_s``)."""


def read(ctx):
    return ctx["host_blocked_ms"]
