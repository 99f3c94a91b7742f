"""The whole step's share of the chip's bf16 peak, in %: the model FLOPs
of the samples served in the window (flops.sample_flops: real samples
only, no padded lane or tick, nothing recomputed) over the window's
seconds (host clock) times chips times the peak."""

from benchmarks.chip import flops


def read(ctx):
    if not ctx.peaks or not ctx.samples or ctx.window_s <= 0:
        return None
    f = ctx.samples * flops.sample_flops(ctx.model, ctx.n_ways)
    return 100.0 * f / (ctx.window_s * ctx.chips
                        * ctx.peaks["bf16_flops_per_s"])
