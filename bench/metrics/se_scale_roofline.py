"""Roofline share of the se_scale kernel's calls in the traced window:
the least time of the calls named ``se_scale.<node>`` (the larger of
their operations over the chip's peak and their bytes over its memory
bandwidth, ``bench/kernels/se_scale.py`` at float32, the configuration's
dtype) over their summed device time, in %.  The calls are picked by
name: ``bench/kernels`` gives them no kind of their own."""

from bench.kernels import load

PREFIX = "se_scale."
ITEMSIZE = 4  # float32


def read(ctx):
    kernel = load("se_scale")
    took = least = compute = memory = 0.0
    for _, name, start, end in ctx.trace.ops:
        call = ctx.trace.calls.get(name)
        if (not name.startswith(PREFIX) or call is None
                or not kernel.matches(call.operands, call.result)):
            continue
        took += (end - start) * 1e-9
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
    ctx.log(f"se_scale: kernel {took:.6f} s, least {least:.6f} s "
            f"(compute-bound {compute:.6f} s, memory-bound {memory:.6f} s)")
    return 100.0 * least / took
