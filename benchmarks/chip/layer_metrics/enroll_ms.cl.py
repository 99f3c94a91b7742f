"""Median ``enroll`` span of the service (sessions/service.py): the shot
embedding, the paged bank's running-mean update and the wait for it on
the device, in ms."""

import statistics


def read(ctx):
    d = [e["dur"] for e in ctx.spans_named("enroll")]
    return statistics.median(d) / 1e3 if d else None
