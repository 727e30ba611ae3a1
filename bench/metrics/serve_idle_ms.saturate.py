"""Mean device-idle ms inside each serve call of the saturate traffic."""

from bench.metrics import serve_idle_ms


def read(ctx):
    return serve_idle_ms(ctx)
