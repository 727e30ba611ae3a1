"""The split of a serve call into the engine's host steps
(``bench/engine_split.py``): synthetic windows with planted device ops
and engine spans, the recorded chip trace with engine spans appended, and
a traced window on the CPU at a small size."""

import gzip
import json
import os

import pytest

from bench import engine_split, harness, trace

HERE = os.path.dirname(os.path.abspath(__file__))
PEAK = harness.peak_of("TPU v5 lite")


def _call(lo, build=(1, 3), batches=((3, 7, 8, 15), (15, 18, 19, 24)), end=26):
    """One planted ``bench_serve`` call from ``lo`` (units of 100 ns):
    serve_frames from lo+0.5 to lo+end, a build, and per batch an ingest,
    a dispatch and a fetch (ingest from a to b, dispatch b to c, fetch c
    to e)."""
    u = 100.0
    spans = [("bench_serve", lo * u, (lo + end + 1) * u, {}),
             ("serve_frames", (lo + 0.5) * u, (lo + end) * u, {}),
             ("build", (lo + build[0]) * u, (lo + build[1]) * u, {"hit": True})]
    for bid, (a, b, c, e) in enumerate(batches):
        spans += [("ingest", (lo + a) * u, (lo + b) * u, {"bid": bid}),
                  ("dispatch", (lo + b) * u, (lo + c) * u, {"bid": bid}),
                  ("fetch", (lo + c) * u, (lo + e) * u, {"bid": bid})]
    return spans


def _window(n_calls=2):
    spans = [("bench_window", 0.0, 100.0 * 30 * n_calls, {})]
    ops = []
    for k in range(n_calls):
        lo = 30 * k
        spans += _call(lo)
        # the device runs inside each fetch: 200 ns then 400 ns of work
        ops += [("/device:TPU:0", "fusion.1", (lo + 9) * 100.0, (lo + 11) * 100.0),
                ("/device:TPU:0", "fusion.2", (lo + 19) * 100.0, (lo + 23) * 100.0)]
    return trace.Reduction(ops, spans, PEAK)


def test_split_of_a_planted_window():
    got = engine_split.split(_window())
    assert got["calls"] == 2
    # per call, in ms: ingest 4 + 3, dispatch 1 + 1, fetch 7 + 5 units
    assert got["ingest_ms"] == pytest.approx(700e-6)
    assert got["dispatch_ms"] == pytest.approx(200e-6)
    assert got["fetch_ms"] == pytest.approx(1200e-6)
    assert got["build_ms"] == pytest.approx(200e-6)
    # serve_frames 25.5 units less its children 23 units
    assert got["engine_self_ms"] == pytest.approx(250e-6)
    assert got["call_ms"] == pytest.approx(2700e-6)
    assert got["client_ms"] == pytest.approx(150e-6)
    steps = ("ingest", "dispatch", "fetch", "engine_self", "build", "client")
    assert sum(got[f"{s}_ms"] for s in steps) == pytest.approx(got["call_ms"])
    # the device works inside the fetches only: fetch 0 (8-15) waits 1
    # unit for the op at 9-11 and 4 after it, fetch 1 (19-24) 0 and 1
    assert got["fetch_idle_ms"] == pytest.approx(600e-6)
    assert got["fetch_head_idle_ms"] == pytest.approx(100e-6)
    assert got["fetch_tail_idle_ms"] == pytest.approx(500e-6)
    for s in ("ingest", "dispatch", "engine_self", "build"):
        assert got[f"{s}_idle_ms"] == pytest.approx(got[f"{s}_ms"])
    assert got["call_idle_ms"] == pytest.approx(2100e-6)


def test_node_times_read_the_kernel_names():
    ops = [("/device:TPU:0", "kpu_conv.l1b1_conv1.1", 0.0, 300.0),
           ("/device:TPU:0", "fcu_matmul.conv1.1", 300.0, 400.0),
           ("/device:TPU:0", "kpu_conv.l1b1_conv1.2", 400.0, 500.0),
           ("/device:TPU:0", "copy.291", 500.0, 650.0)]
    red = trace.Reduction(ops, [("bench_window", 0.0, 1000.0, {})], PEAK)
    got = engine_split.node_times(red)
    assert [k for k, _ in got] == ["l1b1_conv1", "xla", "conv1"]
    assert [v for _, v in got] == pytest.approx([400e-9, 150e-9, 100e-9])


def test_steps_sum_to_the_call_without_build_or_client_code():
    """serve_frames as long as bench_serve and no build: the four steps
    add up to the planted call."""
    red = _window()
    spans = []
    for name, s, e, args in red.spans:
        if name == "build":
            continue
        if name == "serve_frames":
            s, e = s - 50.0, e + 100.0
        spans.append((name, s, e, args))
    red = trace.Reduction([(c, n, s, e) for c, n, s, e in red.ops],
                          [("bench_window", red.lo, red.hi, {})] + spans, PEAK)
    got = engine_split.split(red)
    four = sum(got[f"{s}_ms"] for s in ("ingest", "dispatch", "fetch", "engine_self"))
    assert four == pytest.approx(got["call_ms"])
    assert got["client_ms"] == pytest.approx(0.0, abs=1e-12)


def test_idle_gaps_are_named_by_the_innermost_engine_span():
    """A gap is named by the innermost span at its midpoint: an engine
    step where one runs, the client's call where serve_frames has ended."""
    gaps = _window().idle_gaps()
    assert [name for name, _ in gaps] == [
        "build", "ingest", "ingest", "ingest", "bench_serve"]
    assert [g[1] for g in gaps] == pytest.approx([1.6e-6, 9e-7, 8e-7, 8e-7, 7e-7])
    assert _window().busy_outside_s("serve_frames") == 0


def test_recorded_chip_trace_reduces_the_same_with_engine_spans():
    """Appending engine spans inside the recorded call leaves every
    number the benchmark's readers take unchanged."""
    with gzip.open(os.path.join(HERE, "data", "v5e_resnet18_serve_call.json.gz"), "rt") as f:
        rec = json.load(f)
    ops = [tuple(o) for o in rec["ops"]]
    spans = [tuple(s) for s in rec["spans"]]
    (_, lo, hi, _), = [s for s in spans if s[0] == "bench_serve"]
    step = (hi - lo) / 40
    engine = [("serve_frames", lo + 1.0, hi - 1.0, {})]
    for bid in range(8):
        a = lo + (4 * bid + 2) * step
        engine += [("ingest", a, a + step, {"bid": bid}),
                   ("dispatch", a + step, a + 2 * step, {"bid": bid}),
                   ("fetch", a + 2 * step, a + 4 * step, {"bid": bid})]
    plain = trace.Reduction(ops, spans, PEAK)
    both = trace.Reduction(ops, spans + engine, PEAK)
    assert both.busy_s == plain.busy_s
    assert both.span_idle_s("bench_serve") == plain.span_idle_s("bench_serve")
    assert both.busy_outside_s("bench_serve") == plain.busy_outside_s("bench_serve") == 0
    for kind in ("kpu_conv", "fcu_matmul"):
        assert both.kernel_time(kind) == plain.kernel_time(kind)
        assert both.least_time(kind) == plain.least_time(kind)
    assert both.top_ops() == plain.top_ops()
    assert [g[1] for g in both.idle_gaps()] == [g[1] for g in plain.idle_gaps()]
    assert {g[0] for g in both.idle_gaps()} <= {"serve_frames", "ingest", "dispatch", "fetch"}
    got = engine_split.split(both)
    assert got["calls"] == 1
    assert got["call_ms"] == pytest.approx((hi - lo) * 1e-6)


@pytest.fixture(scope="module")
def served():
    """resnet18 through the served rate-matched path at 32x32, micro-batch
    2, 4 frames a call from a pool of 8 (Pallas kernels interpreted)."""
    cell = harness.load_cell("resnet18.saturate")
    cell.config.update(input_hw=[32, 32], num_classes=10, microbatch=2)
    cell.mix.update(pool_frames=8, frames_per_call=4)
    return harness.setup(cell, 2**31 + 19)


def test_traced_window_appends_engine_spans(served, tmp_path):
    """On the CPU: the window reuses the warmed programs, and each serve
    call holds one serve_frames span with two micro-batches' steps."""
    window, spans = engine_split.traced_window(served, 3, 0.3, str(tmp_path))
    assert window.lowered == 0
    assert served.serve_config.trace is None  # the untraced path is untouched
    calls = [s for s in spans if s[0] == "bench_serve"]
    assert len(calls) == len(window.calls) >= 1
    for _, lo, hi, _ in calls:
        inside = [s[0] for s in spans if lo <= s[1] <= s[2] <= hi]
        assert inside.count("serve_frames") == 1 and inside.count("build") == 1
        for step in ("ingest", "dispatch", "fetch"):
            assert inside.count(step) == 2
    assert not [s for s in spans if s[0] in ("plan", "pipeline_builds")]
    red = trace.Reduction([], spans, PEAK)
    got = engine_split.split(red)
    assert got["calls"] == len(calls)
    assert 0 < got["engine_self_ms"] < got["call_ms"]
    assert engine_split.record_cost_s(calls=5) > 0
