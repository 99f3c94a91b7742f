"""Share of its roofline the fused block kernel reaches (kernels/
tcn_block.py), in %: the least time the chip's peaks allow for the
algorithm's FLOPs and bytes of every block call in the window (flops.py,
with the live lanes and tick length of each ``dispatch`` span), over the
kernel's measured device time.  The result's ``bound`` names the roofline
(compute or memory) that bounds most of that least time."""

from benchmarks.chip import flops, trace_reduce


def read(ctx):
    d = ctx.spans_named("dispatch")
    if not ctx.trace or not ctx.peaks or not d:
        return None
    kernel_s, n = trace_reduce.op_time(ctx.trace, flops.TCN_BLOCK_OPS)
    if kernel_s <= 0 or n != flops.kernel_calls(ctx.model, d, ctx.chips):
        return None     # not one kernel event per block, dispatch and chip
    counts = []
    for e in d:
        T = int(str(e["args"]["shape"]).lstrip("T"))
        counts += flops.chunk_kernel_counts(ctx.model, T, e["args"]["lanes"])
    least, bound = flops.least_time(counts, ctx.peaks)
    return 100.0 * least / kernel_s, {"bound": bound}
