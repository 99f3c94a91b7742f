"""Host time per dispatch outside the service's ``dispatch`` spans: the
window (host clock) less the summed ``dispatch`` spans, over the number of
dispatches, in ms.  It is what the plane and the clients cost each chunk
step."""


def read(ctx):
    d = [e["dur"] for e in ctx.spans_named("dispatch")]
    if not d:
        return None
    return (ctx.window_s - sum(d) * 1e-6) / len(d) * 1e3
