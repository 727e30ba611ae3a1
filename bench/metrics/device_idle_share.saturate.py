"""Share of the traced window of the saturate traffic with the device idle."""

from bench.metrics import idle_share


def read(ctx):
    return idle_share(ctx)
