"""Median ``fetch`` span of the service (sessions/service.py): the copies
of a dispatch's results back to the host, once the device is done, in
ms."""

import statistics


def read(ctx):
    d = [e["dur"] for e in ctx.spans_named("fetch")]
    return statistics.median(d) / 1e3 if d else None
