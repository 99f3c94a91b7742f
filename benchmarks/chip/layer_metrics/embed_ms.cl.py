"""Median ``embed`` span of the service (sessions/service.py): the
enrollment's shot embedding, through the wait for it on the device, in
ms."""

import statistics


def read(ctx):
    d = [e["dur"] for e in ctx.spans_named("embed")]
    return statistics.median(d) / 1e3 if d else None
