"""Share of the device's busy time in the traced window spent in the
squeeze-and-excitation nodes' named calls, in %: ``se_scale.<node>``
(the gate's multiply) and ``fcu_matmul.<block>_se_reduce`` /
``_se_expand`` (its two dense layers).  The gate's XLA ops (the pooling
reduce, the bias and activation fusions) carry no name in the trace and
are not counted."""

import re

SE_CALL = re.compile(r"^(se_scale\.|fcu_matmul\.\w+_se_(reduce|expand)(\.|$))")


def read(ctx):
    took = sum(end - start for _, name, start, end in ctx.trace.ops
               if SE_CALL.match(name)) * 1e-9
    if took <= 0 or ctx.trace.busy_s <= 0:
        return None
    return 100.0 * took / (ctx.trace.busy_s * ctx.trace.chips)
