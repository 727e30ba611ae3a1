"""Roofline share of the fcu_matmul kernel's calls in the traced window."""

from bench.metrics import roofline


def read(ctx):
    return roofline(ctx, "fcu_matmul")
