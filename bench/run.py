"""Run one cell of the on-chip benchmark and print its result line.

    python3 bench/run.py --workload resnet18.saturate --seed 7 \\
        --seconds 20 --trace 0

A cell (``BENCHMARK.json`` ``workloads``) is a model configuration
(``bench/configs/<config>.json``) under a traffic mix
(``bench/traffic/<mix>.json``).  The run makes the weights and frames
from ``--seed``, warms up, drives the traffic through ``CNNApi.serve``
for ``--seconds``, then compares every returned frame's logits with the
plain reference.  ``--trace 1`` profiles a window of at most
``TRACE_SECONDS`` instead and reports the per-layer metrics
(``bench/metrics``).

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown``
when traced), and last ``check``, each compared number beside its limit;
the same numbers end standard error.  No TPU, fewer chips than the cell
asks for, or a chip without peaks in ``bench/peaks.json``: exit code 2
and no result line.  The compile cache is ``JAX_COMPILATION_CACHE_DIR``
where set, else ``.jax_cache/`` in the checkout.
"""

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import jax  # noqa: E402

from bench import harness, metrics, trace  # noqa: E402
from bench.harness import BenchError, log  # noqa: E402


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def per_layer(cell, served, window, reduction, peak) -> dict:
    ctx = harness.TraceContext(reduction, window.due, served.flops_per_frame,
                               peak, log)
    out = {}
    for m in cell.metrics("per_layer"):
        value = metrics.load(m["name"]).read(ctx)
        if value is None:
            # a kernel taken off the path leaves its roofline silent; say so
            log(f"per-layer metric {m['name']} read nothing in this trace")
        else:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def _finite(value: float):
    """Standard JSON has no infinity: a non-finite reading, such as the
    p99 when frames never came back, is printed as null (and the run is
    then not correct)."""
    return value if math.isfinite(value) else None


def run(args) -> dict:
    cell = harness.load_cell(args.workload)
    devices, peak = harness.tpu_devices(cell.workload["chips"])
    cache = harness.use_compile_cache()
    log(f"device {devices[0].device_kind} x{len(devices)}, compile cache {cache}")
    served = harness.setup(cell, args.seed)
    reduction = None
    if args.trace:
        with tempfile.TemporaryDirectory() as logdir:
            window, spans = harness.traced_window(
                served, args.seed, min(args.seconds, harness.TRACE_SECONDS), logdir)
            ops = trace.read_ops(
                jax.profiler.ProfileData.from_file(trace.xplane_path(logdir)))
        reduction = trace.Reduction(ops, spans, peak)
        log(f"device busy outside serve calls {reduction.busy_outside_s('bench_serve'):.6f} s "
            f"of {reduction.busy_s:.6f} s (the host spans' alignment with the trace)")
    else:
        window = harness.measure(served, args.seed, args.seconds)
    setup_s = window.start - PROCESS_START
    memory = harness.peak_bytes(devices)
    log(f"setup_s {setup_s:.3f} split {json.dumps(served.setup_split)}; "
        f"window {window.seconds:.3f} s, {window.due} frames in "
        f"{len(window.calls)} calls, {window.lowered} programs lowered in it")
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": memory}
    if args.trace:
        found = per_layer(cell, served, window, reduction, peak)
        device.update(busy_s=reduction.busy_s, window_s=reduction.window_s)
    else:
        found = {m["name"]: {"value": _finite(harness.end_to_end(m["name"], window, setup_s)),
                             "unit": m["unit"]}
                 for m in cell.metrics("end_to_end")}
    served.api.caches["pipelines"].clear()  # free the program's state
    t = time.perf_counter()
    verdict = harness.verify(served, window)
    log(f"reference {time.perf_counter() - t:.3f} s")
    result = {"correct": verdict["correct"], "attempted": verdict["attempted"],
              "failed": verdict["failed"], "metrics": found, "device": device}
    if args.trace:
        result["breakdown"] = {"device_ops": reduction.top_ops(),
                               "idle_gaps": reduction.idle_gaps()}
    result["check"] = verdict["check"]
    return result


def main(argv=None) -> int:
    args = parse(argv)
    try:
        result = run(args)
    except BenchError as e:
        log(f"bench: {e}")
        return 2
    for name, c in result["check"].items():
        log(f"check {name} {c['value']} limit {c['limit']}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
