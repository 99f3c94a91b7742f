"""p95 of the ack delay of the window's enrollments, in ms: the
``held_us`` of each successful ``enroll`` ``plane_op`` event (serving/
plane.py), from the enrollment's result to the end of its cycle, when
the client can resume.  Exact, over every enrollment of the window."""

import numpy as np


def read(ctx):
    h = [e["args"]["held_us"] for e in ctx.spans_named("plane_op")
         if e["args"].get("kind") == "enroll" and e["args"].get("ok")]
    return float(np.percentile(h, 95)) / 1e3 if h else None
