"""Roofline share of the grouped convolutions' calls in the traced
window: the least time of the ``kpu_conv``-shaped calls whose weight
reads fewer input channels than the frame holds (operand 1's dim 2 <
operand 0's dim 4: the grouped route) — the larger of their operations
over the chip's peak and their bytes over its memory bandwidth,
``bench/kernels/kpu_conv.py`` at float32, the configuration's dtype —
over their summed device time, in %.  The calls are picked by shape,
whatever their nodes are named."""

from bench.kernels import load

ITEMSIZE = 4  # float32


def grouped_calls(ctx):
    """(call, device seconds) of every grouped ``kpu_conv`` call."""
    kernel = load("kpu_conv")
    for _, name, start, end in ctx.trace.ops:
        call = ctx.trace.calls.get(name)
        if (call is not None and kernel.matches(call.operands, call.result)
                and call.operands[1][2] < call.operands[0][4]):
            yield call, (end - start) * 1e-9


def read(ctx):
    kernel = load("kpu_conv")
    took = least = compute = memory = 0.0
    for call, t in grouped_calls(ctx):
        took += t
        tc = kernel.flops(call.operands, call.result) / ctx.peak["flops"]
        tm = kernel.bytes_moved(call.operands, call.result, ITEMSIZE) / (
            ctx.peak["hbm_bytes_per_s"])
        least += max(tc, tm)
        if tc >= tm:
            compute += tc
        else:
            memory += tm
    if took <= 0:
        return None
    ctx.log(f"grouped kpu_conv: kernel {took:.6f} s, least {least:.6f} s "
            f"(compute-bound {compute:.6f} s, memory-bound {memory:.6f} s)")
    return 100.0 * least / took
