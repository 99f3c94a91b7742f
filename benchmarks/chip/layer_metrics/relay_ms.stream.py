"""Device time per chunk step outside the fused block kernel's events
(kernels/tcn_block.py): the ring re-lay gathers, the heads and copies of
core/streaming.make_fused_chunk, in ms per dispatch."""

from benchmarks.chip import flops, trace_reduce


def read(ctx):
    d = ctx.spans_named("dispatch")
    if not ctx.trace or not d:
        return None
    kernel_s, n = trace_reduce.op_time(ctx.trace, flops.TCN_BLOCK_OPS)
    if n != flops.kernel_calls(ctx.model, d, ctx.chips):
        return None     # not one kernel event per block, dispatch and chip
    return (ctx.trace["busy_s"] * ctx.trace["n_devices"] - kernel_s) \
        / ctx.trace["n_devices"] / len(d) * 1e3
