"""The EfficientNet-B0 cell: the plain reference against the served
program's executor, its published operations, and the squeeze-and-
excitation readers (``bench/kernels/se_scale.py``,
``bench/metrics/se_scale_roofline.py`` and ``se_share.py``)."""

import functools

import jax
import numpy as np
import pytest

from bench import check, harness, metrics, trace
from bench.kernels import KINDS, load, mosaic_calls
from bench.reference import load as load_reference, ops
from repro.models.registry import get_cnn_api

PEAK = harness.peak_of("TPU v5 lite")
SMALL = {"input_hw": [32, 32], "num_classes": 10}
FULL = {"input_hw": [224, 224], "num_classes": 1000}
REF = load_reference("efficientnet_b0")


def test_reference_matches_executor():
    """At the highest matmul precision on the CPU the two differ by
    float32 re-association alone (~1e-7 relative a layer, 65 layers)."""
    params = ops.init(REF.layers(SMALL), jax.random.key(3))
    x = np.random.default_rng(0).standard_normal((2, 32, 32, 3), np.float32)
    want = jax.jit(functools.partial(REF.forward, cfg=SMALL))(params, x)
    api = get_cnn_api("efficientnet_b0")
    cfg = api.make_config(input_hw=(32, 32), num_classes=10)
    with jax.default_matmul_precision("highest"):
        got = api.apply(params, x, cfg)
    assert check.frame_errors(np.asarray(got), np.asarray(want)).max() <= 1e-5


def test_flops_per_frame_are_published():
    """385,814,752 multiply-adds a 224x224 frame: Tan & Le's "0.39B
    FLOPS" (they count multiply-adds).  Pointwise convs hold 338.5 M of
    them, the 5x5 depthwise 21.5 M, the 3x3 depthwise 13.0 M; the gate's
    multiply is not a multiply-add."""
    layers = REF.layers(FULL)
    assert ops.macs(layers) == 385_814_752
    by = {}
    for layer in layers:
        key = (layer.kind, layer.k)
        by[key] = by.get(key, 0) + layer.macs
    assert round(by[("pointwise", 1)] / 1e6, 1) == 338.5
    assert round(by[("dwconv", 5)] / 1e6, 1) == 21.5
    assert round(by[("dwconv", 3)] / 1e6, 1) == 13.0
    assert round(sum(np.prod(la.w_shape) + la.d_out for la in layers) / 1e6, 2) == 5.27


def test_layers_match_program_graph():
    api = get_cnn_api("efficientnet_b0")
    params = jax.eval_shape(
        lambda: api.init(api.make_config(input_hw=(224, 224)), jax.random.key(0)))
    mine = {layer.name: layer.w_shape for layer in REF.layers(FULL)}
    assert mine == {name: p["w"].shape for name, p in params.items()}


# b1's gate at 112x112x32, micro-batch 8: one multiply a feature
SE_CALL = ([(8, 112, 112, 32), (8, 1, 32)], (8, 112, 112, 32))
SE_BYTES = 4 * (2 * 8 * 112 * 112 * 32 + 8 * 32)


def test_se_scale_shapes_match_no_other_kind():
    operands, result = SE_CALL
    se = load("se_scale")
    assert se.matches(operands, result)
    assert not any(load(k).matches(operands, result) for k in KINDS)
    assert se.flops(operands, result) == 8 * 112 * 112 * 32
    assert se.bytes_moved(operands, result, 4) == SE_BYTES
    # and it takes no other kind's call for its own
    assert not se.matches([(8, 1, 114, 114, 32), (3, 3, 32)], (8, 112, 112, 32))
    assert not se.matches([(8, 96), (96, 4)], (8, 4))


HLO = (
    '%se_scale.b1_scale.1 = f32[8,112,112,32]{3,2,1,0:T(8,128)} custom-call('
    'f32[8,112,112,32]{3,2,1,0:T(8,128)} %fusion.7, f32[8,1,32]{2,1,0:T(1,128)} '
    '%bitcast.2), custom_call_target="tpu_custom_call", '
    'operand_layout_constraints={f32[8,112,112,32]{3,2,1,0}, f32[8,1,32]{2,1,0}}, '
    'frontend_attributes={kernel_metadata={}}'
)
FCU = (
    '%fcu_matmul.b1_se_reduce.1 = f32[8,8]{1,0:T(8,128)} custom-call('
    'f32[8,32]{1,0:T(8,128)} %reduce.3, f32[32,8]{1,0:T(8,128)} %w.3), '
    'custom_call_target="tpu_custom_call", '
    'operand_layout_constraints={f32[8,32]{1,0}, f32[32,8]{1,0}}, '
    'frontend_attributes={kernel_metadata={}}'
)


def _context():
    """A made-up 1,000 ns window: the gate's call 100-400 ns, its reduce
    matmul 400-450, a projection matmul 450-600, an XLA fusion 600-700."""
    ops_ = [
        ("/device:TPU:0", HLO, 100.0, 400.0),
        ("/device:TPU:0", FCU, 400.0, 450.0),
        ("/device:TPU:0", "fcu_matmul.b1_project.1", 450.0, 600.0),
        ("/device:TPU:0", "fusion.9", 600.0, 700.0),
    ]
    spans = [("bench_window", 0.0, 1000.0, {}), ("bench_serve", 0.0, 1000.0, {})]
    red = trace.Reduction(ops_, spans, PEAK)
    return harness.TraceContext(red, 8, 1, PEAK, lambda _: None)


def test_se_calls_keep_kind_mosaic():
    calls = mosaic_calls(HLO)
    call = calls["se_scale.b1_scale.1"]
    assert call.kind == "mosaic"
    assert (list(call.operands), call.result) == SE_CALL
    assert mosaic_calls(FCU)["fcu_matmul.b1_se_reduce.1"].kind == "fcu_matmul"


def test_readers_pick_the_named_calls():
    ctx = _context()
    roof = metrics.load("se_scale_roofline").read(ctx)
    least = max(8 * 112 * 112 * 32 / PEAK["flops"], SE_BYTES / PEAK["hbm_bytes_per_s"])
    assert roof == pytest.approx(100 * least / 300e-9)
    # the gate's call and its reduce matmul, over 600 ns busy
    share = metrics.load("se_share").read(ctx)
    assert share == pytest.approx(100 * 350 / 600)


def test_readers_read_nothing_without_se_calls():
    """A trace of a model without gates (or of a program without the
    kernel): both readers return None and do not raise."""
    ops_ = [("/device:TPU:0", "fcu_matmul.b1_project.1", 0.0, 100.0)]
    red = trace.Reduction(ops_, [("bench_window", 0.0, 1000.0, {})], PEAK)
    ctx = harness.TraceContext(red, 8, 1, PEAK, lambda _: None)
    assert metrics.load("se_scale_roofline").read(ctx) is None
    assert metrics.load("se_share").read(ctx) is None
