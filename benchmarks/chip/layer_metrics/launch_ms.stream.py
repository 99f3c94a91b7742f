"""Median ``launch`` span of the service (sessions/service.py): the call of
the jitted chunk step until it returns, that is the runtime's dispatch of
the program, before any wait for the device, in ms."""

import statistics


def read(ctx):
    d = [e["dur"] for e in ctx.spans_named("launch")]
    return statistics.median(d) / 1e3 if d else None
