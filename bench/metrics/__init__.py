"""Per-layer metric readers.  One module per metric,
``bench/metrics/<metric>.py``, found by the metric's name in
``BENCHMARK.json``; each defines ``read(ctx)`` and returns a number, or
None when the trace holds nothing for it to read.

``ctx`` is a ``bench.harness.TraceContext``: ``ctx.trace`` (a
``bench.trace.Reduction`` of the traced window), ``ctx.frames`` (frames
returned to the client inside that window), ``ctx.flops_per_frame`` (the
published architecture's, from ``bench/reference``), ``ctx.peak``
(``bench/peaks.json``'s entry for the chip) and ``ctx.log`` (prints a
line to standard error).
"""

from __future__ import annotations

import importlib.util
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def load(name: str):
    path = os.path.join(HERE, f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench.metrics.{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def roofline(ctx, kind: str):
    """Least time over measured time of one kernel kind's calls, in %."""
    took = ctx.trace.kernel_time(kind)
    if took <= 0:
        return None
    least, compute, memory = ctx.trace.least_time(kind)
    ctx.log(f"{kind}: kernel {took:.6f} s, least {least:.6f} s "
            f"(compute-bound {compute:.6f} s, memory-bound {memory:.6f} s)")
    return 100.0 * least / took


def serve_idle_ms(ctx):
    """Mean device-idle milliseconds inside the client's serve calls."""
    idle = ctx.trace.span_idle_s("bench_serve")
    if not idle or ctx.trace.chips == 0:
        return None
    return 1e3 * sum(idle) / len(idle)


def idle_share(ctx):
    """Share of the traced window in which no operation ran, in %."""
    if ctx.trace.chips == 0:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s / ctx.trace.window_s)
