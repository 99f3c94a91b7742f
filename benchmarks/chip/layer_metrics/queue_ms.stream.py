"""p95 of the plane's queue wait for the window's pushes, in ms: the
``queue_us`` of each successful ``push`` ``plane_op`` event (serving/
plane.py), from enqueue to the cycle that took the push off the queue.
Exact, over every push of the window."""

import numpy as np


def read(ctx):
    q = [e["args"]["queue_us"] for e in ctx.spans_named("plane_op")
         if e["args"].get("kind") == "push" and e["args"].get("ok")]
    return float(np.percentile(q, 95)) / 1e3 if q else None
