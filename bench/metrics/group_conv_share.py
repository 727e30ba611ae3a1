"""Share of the device's busy time in the traced window spent in the
grouped convolutions' calls (the ``kpu_conv``-shaped calls whose weight
reads fewer input channels than the frame holds; see
``group_conv_roofline``), in %."""

from bench.metrics import load


def read(ctx):
    took = sum(t for _, t in load("group_conv_roofline").grouped_calls(ctx))
    if took <= 0 or ctx.trace.busy_s <= 0:
        return None
    return 100.0 * took / (ctx.trace.busy_s * ctx.trace.chips)
