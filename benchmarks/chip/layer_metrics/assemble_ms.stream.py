"""Median ``assemble`` span of the service (sessions/service.py): building
each pushed session's result from the slices of its ticks, in ms."""

import statistics


def read(ctx):
    d = [e["dur"] for e in ctx.spans_named("assemble")]
    return statistics.median(d) / 1e3 if d else None
