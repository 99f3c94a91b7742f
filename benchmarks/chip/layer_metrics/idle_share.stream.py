"""Share of the traced window in which no operation ran on the device:
1 - busy / window, from the profiler trace, in %."""


def read(ctx):
    t = ctx.trace
    if not t or not t["busy_s"] or not t["window_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
