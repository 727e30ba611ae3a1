"""Roofline share of the dw_conv kernel's calls in the traced window."""

from bench.metrics import roofline


def read(ctx):
    return roofline(ctx, "dw_conv")
