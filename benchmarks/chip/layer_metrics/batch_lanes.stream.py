"""Mean lanes per grid dispatch in the window: sum over count of the
plane's ``plane_batch_lanes`` histogram (serving/plane.py)."""


def read(ctx):
    n, total = ctx.hist_delta("plane_batch_lanes")
    return total / n if n else None
