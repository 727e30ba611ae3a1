"""Split each serve call of a traced window into the engine's host steps
(run on the chip).

    python3 bench/engine_split.py --workload resnet18.saturate --seed 7 \\
        --seconds 3

The benchmark's traced window (``harness.traced_window``) records the
client's spans alone.  This one passes one host-only ``obs.Tracer`` to
the served path for the window, so the engine records its own host
spans (``serving/cnn_stream.py``: ``serve_frames``, ``plan``, ``build``,
``ingest``, ``dispatch``, ``fetch``) on the clock the client's spans use,
and appends them to the client's.  The warmed programs are reused: the
tracer is not part of the pipeline cache's key.

Printed, one JSON line: per ``bench_serve`` call the mean ms of each step
(``ingest_ms``, ``dispatch_ms``, ``fetch_ms``, ``engine_self_ms`` — the
``serve_frames`` span less its child spans: the tick loop, ``submit_all``
and the stacking of outputs — and ``build_ms``, ``plan_ms``,
``client_ms``, the rest of the call) and the device-idle ms inside each;
the benchmark's own per-layer metrics of the cell, read from the same
window; the graph nodes whose kernels took the most device time; and
what recording the spans costs per call.  The benchmark's own runs never
run this.
"""

import argparse
import collections
import dataclasses
import json
import os
import re
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import jax  # noqa: E402

from bench import harness, metrics, trace  # noqa: E402
from bench.kernels import KINDS  # noqa: E402
from bench.harness import log  # noqa: E402

CALL = "bench_serve"
ENGINE = "serve_frames"
STEPS = ("ingest", "dispatch", "fetch")
CHILDREN = ("plan", "build") + STEPS
KERNEL = re.compile(rf"^({'|'.join(KINDS)})\.(.+?)(\.\d+)?$")


def engine_spans(tracer, origin: int) -> list:
    """The tracer's host spans in ``harness.HostSpans``' form: (name,
    start ns, end ns, args) from ``origin``, the same clock."""
    return [(s.name, float(s.start - origin), float(s.end - origin), dict(s.args))
            for s in tracer.spans(clock="host")]


def traced_window(served, seed: int, seconds: float, logdir: str):
    """``harness.traced_window`` with the engine's spans appended to the
    client's."""
    from repro.obs import Tracer

    tracer = Tracer(clocks=("host",))
    traced = dataclasses.replace(
        served, serve_config=served.serve_config.with_(trace=tracer))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 0
    spans = harness.HostSpans()
    jax.profiler.start_trace(logdir, profiler_options=opts)
    try:
        window = harness.measure(traced, seed, seconds, span=spans)
    finally:
        jax.profiler.stop_trace()
    return window, spans.spans + engine_spans(tracer, spans.origin)


def _busy(red, s, e) -> float:
    return sum(trace.covered(m, s, e) for m in red.merged.values()) / max(red.chips, 1)


def _edges_idle(red, s, e) -> tuple:
    """Device-idle ns at the start and at the end of [s, e] (the first
    chip's), before its first and after its last operation."""
    inside = [(max(a, s), min(b, e)) for a, b in red.merged[sorted(red.merged)[0]]
              if a < e and b > s] if red.merged else []
    if not inside:
        return e - s, 0.0
    return inside[0][0] - s, e - inside[-1][1]


def split(red) -> dict:
    """Mean ms per ``bench_serve`` call of each engine step, and of the
    device's idle time inside it (``<step>_idle_ms``).  The child spans
    lie inside ``serve_frames``, whose self time is what they leave.
    ``fetch_head_idle_ms`` / ``fetch_tail_idle_ms``: the fetches' idle
    before the device's first operation in them (the program waiting to
    start) and after its last (the copy back to the host)."""
    calls = [sp for sp in red.spans if sp[0] == CALL]
    if not calls:
        return {}
    total = collections.Counter()

    def add(step, s, e, sign=1.0):
        total[f"{step}_ms"] += sign * (e - s)
        total[f"{step}_idle_ms"] += sign * ((e - s) - _busy(red, s, e))

    for _, lo, hi, _ in calls:
        add("call", lo, hi)
        for name, s, e, _ in red.spans:
            if not lo <= s <= e <= hi:
                continue
            if name == ENGINE:
                add("engine_self", s, e)
            elif name in CHILDREN:
                add(name, s, e)
                add("engine_self", s, e, -1.0)
            if name == "fetch":
                head, tail = _edges_idle(red, s, e)
                total["fetch_head_idle_ms"] += head
                total["fetch_tail_idle_ms"] += tail
    out = {k: 1e-6 * v / len(calls) for k, v in sorted(total.items())}
    out["client_ms"] = out["call_ms"] - sum(
        out.get(f"{step}_ms", 0.0) for step in ("engine_self",) + CHILDREN)
    out["calls"] = len(calls)
    return out


def node_times(red, n: int = 10) -> list:
    """Device seconds of the ``n`` graph nodes whose kernels took the
    most: a Pallas call is named ``<kind>.<node>``
    (``kernels.common.kernel_name``) and its instruction numbered
    (``kpu_conv.l1b1_conv1.1``).  XLA's own ops carry no node in the
    trace, and count under ``xla``."""
    per_node = collections.Counter()
    for _, name, s, e in red.ops:
        m = KERNEL.match(name)
        per_node[m.group(2) if m else "xla"] += (e - s) * 1e-9
    return [[k, v] for k, v in per_node.most_common(n)]


def record_cost_s(calls: int = 500, batches: int = 8) -> float:
    """Seconds to record one call's host spans (``batches`` micro-batches:
    ``serve_frames``, ``build`` and three spans per batch) into a
    host-only tracer, as the engine emits them."""
    from repro.obs import Tracer, host_now

    tr = Tracer(clocks=("host",))
    kw = dict(pid="engine", tid="host", clock="host")
    t = time.perf_counter()
    for _ in range(calls):
        tr.begin(ENGINE, host_now(), **kw)
        tr.begin("build", host_now(), **kw)
        tr.end("build", host_now(), hit=True, **kw)
        for bid in range(batches):
            for name in STEPS:
                tr.span(name, host_now(), host_now(), bid=bid, frames=8, **kw)
        tr.end(ENGINE, host_now(), frames=8 * batches, **kw)
    return (time.perf_counter() - t) / calls


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=harness.TRACE_SECONDS)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    devices, peak = harness.tpu_devices(cell.workload["chips"])
    harness.use_compile_cache()
    served = harness.setup(cell, args.seed)
    with tempfile.TemporaryDirectory() as logdir:
        window, spans = traced_window(served, args.seed, args.seconds, logdir)
        ops = trace.read_ops(
            jax.profiler.ProfileData.from_file(trace.xplane_path(logdir)))
    red = trace.Reduction(ops, spans, peak)
    log(f"device busy outside serve calls {red.busy_outside_s(CALL):.6f} s, "
        f"outside {ENGINE} spans {red.busy_outside_s(ENGINE):.6f} s, "
        f"of {red.busy_s:.6f} s; {window.lowered} programs lowered in the window")
    ctx = harness.TraceContext(red, window.due, served.flops_per_frame, peak, log)
    per_layer = {m["name"]: metrics.load(m["name"]).read(ctx)
                 for m in cell.metrics("per_layer")}
    print(json.dumps({
        "workload": args.workload, "seed": args.seed,
        "device": devices[0].device_kind, "frames": window.due,
        "window_s": red.window_s, "busy_s": red.busy_s,
        "lowered": window.lowered, "split": split(red), "per_layer": per_layer,
        "idle_gaps": red.idle_gaps(), "top_nodes": node_times(red),
        "record_cost_ms": 1e3 * record_cost_s(),
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
