"""Median ``dispatch`` span of the service (sessions/service.py): the
jitted chunk call through the copy of its results to the host, in ms."""

import statistics


def read(ctx):
    d = [e["dur"] for e in ctx.spans_named("dispatch")]
    return statistics.median(d) / 1e3 if d else None
