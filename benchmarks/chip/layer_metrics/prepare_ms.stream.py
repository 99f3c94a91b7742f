"""Median ``prepare`` span of the service (sessions/service.py): the host
work before each tick's dispatch (for a push's first tick also the
validation, the binding of parked sessions and the banks' residency):
the input grid and the bank tables, in ms."""

import statistics


def read(ctx):
    d = [e["dur"] for e in ctx.spans_named("prepare")]
    return statistics.median(d) / 1e3 if d else None
