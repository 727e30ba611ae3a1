"""The whole step's share of the chip's peak: the published
architecture's operations for every frame returned in the traced window,
over the window's seconds times the bf16 peak, in %."""


def read(ctx):
    if ctx.frames == 0:
        return None
    return 100.0 * ctx.flops_per_frame * ctx.frames / (
        ctx.trace.window_s * ctx.peak["flops"])
