"""Median ``dispatch`` span of the service (sessions/service.py) for the
query pushes of the continual-learning cell, in ms."""

import statistics


def read(ctx):
    d = [e["dur"] for e in ctx.spans_named("dispatch")]
    return statistics.median(d) / 1e3 if d else None
