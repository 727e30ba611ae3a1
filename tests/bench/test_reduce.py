"""Trace reduction, kernel operation and byte counts, and the peaks
table."""

import collections
import gzip
import json
import os

import pytest

from bench import harness, trace
from bench.kernels import load, mosaic_calls
from bench.reference import load as load_reference, ops

HERE = os.path.dirname(os.path.abspath(__file__))
PEAK = harness.peak_of("TPU v5 lite")

# (kind, operands, result, flops, bytes), counted by hand at float32
CASES = [
    # ResNet-18 layer1 3x3 conv, micro-batch 8: 2*8*56*56*(3*3*64)*64
    ("kpu_conv", [(8, 1, 58, 58, 64), (3, 3, 64, 64)], (8, 56, 56, 64),
     1_849_688_064, 4 * (8 * 58 * 58 * 64 + 3 * 3 * 64 * 64 + 8 * 56 * 56 * 64)),
    # a stride-2 conv's four phases: 2*8*28*28*(3*3*128)*256
    ("kpu_conv", [(8, 4, 29, 29, 128), (3, 3, 128, 256)], (8, 28, 28, 256),
     3_699_376_128, 4 * (8 * 4 * 29 * 29 * 128 + 9 * 128 * 256 + 8 * 28 * 28 * 256)),
    # MobileNetV2 block-1 depthwise at 112x112x32: 2*8*112*112*9*32
    ("dw_conv", [(8, 1, 114, 114, 32), (3, 3, 32)], (8, 112, 112, 32),
     57_802_752, 4 * (8 * 114 * 114 * 32 + 9 * 32 + 8 * 112 * 112 * 32)),
    # ResNet-18 stem as im2col patches: 2*(8*112*112)*147*64
    ("fcu_matmul", [(100352, 147), (147, 64)], (100352, 64),
     1_888_223_232, 4 * (100352 * 147 + 147 * 64 + 100352 * 64)),
]


@pytest.mark.parametrize("kind, operands, result, flops, nbytes", CASES)
def test_ops_and_bytes_by_hand(kind, operands, result, flops, nbytes):
    mod = load(kind)
    assert mod.matches(operands, result)
    others = [k for k in ("kpu_conv", "dw_conv", "fcu_matmul") if k != kind]
    assert not any(load(k).matches(operands, result) for k in others)
    assert mod.flops(operands, result) == flops
    assert mod.bytes_moved(operands, result, 4) == nbytes


HLO = (
    '  %branch_0_fun.22 = f32[8,56,56,64]{3,2,1,0:T(8,128)} custom-call('
    '%pad_bitcast_fusion.3, %w.1), custom_call_target="tpu_custom_call", '
    'operand_layout_constraints={f32[8,1,58,58,64]{4,3,2,1,0}, '
    'f32[3,3,64,64]{3,2,1,0}}, frontend_attributes={kernel_metadata={}}, '
    'metadata={op_name="jit(run_stage)/jit(kpu_conv)/cond/pallas_call"}\n'
    '  %fusion.3 = f32[8,56,56,64]{3,2,1,0} fusion(%x), kind=kLoop\n'
)


def test_mosaic_calls_from_hlo_text():
    calls = mosaic_calls(HLO)
    assert list(calls) == ["branch_0_fun.22"]
    call = calls["branch_0_fun.22"]
    assert call.kind == "kpu_conv"
    assert call.operands == ((8, 1, 58, 58, 64), (3, 3, 64, 64))
    assert call.flops == CASES[0][3] and call.bytes == CASES[0][4]


def test_union_and_cover():
    merged = trace.union([(5, 7), (0, 2), (1, 3), (6, 9)])
    assert merged == [[0, 3], [5, 9]]
    assert trace.covered(merged, 2, 6) == 2
    assert trace.covered(merged, -5, 20) == 7


def test_reduction_of_a_made_up_window():
    calls = mosaic_calls(HLO)
    ops = [("/device:TPU:0", "branch_0_fun.22", 100.0, 400.0),
           ("/device:TPU:0", "fusion.3", 350.0, 500.0),
           ("/device:TPU:0", "fusion.3", 900.0, 1200.0)]  # clipped at 1000
    spans = [("bench_window", 0.0, 1000.0, {}),
             ("bench_serve", 0.0, 600.0, {}), ("bench_serve", 600.0, 1000.0, {})]
    red = trace.Reduction(ops, spans, PEAK, calls)
    assert red.window_s == pytest.approx(1e-6)
    assert red.busy_s == pytest.approx(500e-9)
    assert red.span_idle_s("bench_serve") == pytest.approx([200e-9, 300e-9])
    assert red.kernel_time("kpu_conv") == pytest.approx(300e-9)
    least, compute, memory = red.least_time("kpu_conv")
    assert least == pytest.approx(max(CASES[0][3] / PEAK["flops"],
                                      CASES[0][4] / PEAK["hbm_bytes_per_s"]))
    # 13.5 MB at 819 GB/s outlasts 1.85 GFLOP at 197 TFLOP/s
    assert memory == least and compute == 0
    gaps = red.idle_gaps()
    assert gaps[0] == ["bench_serve", pytest.approx(400e-9)]
    assert [g[1] for g in gaps] == sorted((g[1] for g in gaps), reverse=True)


@pytest.mark.parametrize("text", [
    HLO.splitlines()[0],                      # the whole instruction
    HLO.splitlines()[0].strip().lstrip("%"),  # as TPU trace events carry it
])
def test_reduction_reads_calls_from_whole_instruction_events(text):
    assert trace.op_name(text) == "branch_0_fun.22"
    ops = [("/device:TPU:0", text, 100.0, 400.0),
           ("/device:TPU:0", "fusion.3 = f32[8,56,56,64]{3,2,1,0} fusion(%x)",
            350.0, 500.0)]
    spans = [("bench_window", 0.0, 1000.0, {})]
    red = trace.Reduction(ops, spans, PEAK)
    assert red.kernel_time("kpu_conv") == pytest.approx(300e-9)
    assert red.least_time("kpu_conv")[0] > 0
    assert [label for label, _ in red.top_ops()] == [
        "kpu_conv:branch_0_fun.22", "fusion.3"]


def test_reduction_of_a_recorded_chip_trace():
    """One serve call of ``resnet18.saturate`` (64 frames, 8 micro-batches
    at float32 highest) recorded on a TPU v5 lite: the device ops of the
    traced window and the client's span, custom calls kept whole."""
    with gzip.open(os.path.join(HERE, "data", "v5e_resnet18_serve_call.json.gz"), "rt") as f:
        rec = json.load(f)
    red = trace.Reduction([tuple(o) for o in rec["ops"]],
                          [tuple(s) for s in rec["spans"]], PEAK)
    called = [red.calls[n] for _, n, _, _ in red.ops if n in red.calls]
    # 19 kpu_conv and 2 fcu_matmul calls per micro-batch, whose operations
    # sum to the published architecture's for every frame
    assert sorted(collections.Counter(c.kind for c in called).items()) == [
        ("fcu_matmul", 16), ("kpu_conv", 152)]
    config = harness.load_cell("resnet18.saturate").config
    macs = ops.macs(load_reference("resnet18").layers(config))
    assert sum(c.flops for c in called) == 2 * macs * 64
    for kind in ("kpu_conv", "fcu_matmul"):
        assert 0 < red.least_time(kind)[0] < red.kernel_time(kind)
    assert red.busy_outside_s("bench_serve") == 0
    assert 0 < red.busy_s < red.window_s
    assert red.top_ops()[0][0].startswith(("kpu_conv:", "fcu_matmul:"))
    assert red.idle_gaps()[0][0] == "bench_serve"


def test_unknown_device_kind_raises():
    with pytest.raises(harness.BenchError):
        harness.peak_of("TPU v7x")
    table = json.load(open(os.path.join(os.path.dirname(HERE), "..", "bench",
                                        "peaks.json")))
    assert "TPU v5e" in table["source"]
