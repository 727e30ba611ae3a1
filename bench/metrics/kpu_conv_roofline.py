"""Roofline share of the kpu_conv kernel's calls in the traced window."""

from bench.metrics import roofline


def read(ctx):
    return roofline(ctx, "kpu_conv")
