"""The RegNetY-4.0GF cell: the plain reference against the served
program, its published operations, and the grouped conv's readers
(``bench/metrics/group_conv_roofline.py`` and ``group_conv_share.py``)."""

import functools

import jax
import numpy as np
import pytest

from bench import check, harness, metrics, trace
from bench.kernels import load, mosaic_calls
from bench.reference import load as load_reference, ops
from repro.models.registry import get_cnn_api

PEAK = harness.peak_of("TPU v5 lite")
SMALL = {"input_hw": [32, 32], "num_classes": 10}
FULL = {"input_hw": [224, 224], "num_classes": 1000}
REF = load_reference("regnety_4gf")


def test_flops_per_frame_are_published():
    """3,973,288,448 multiply-adds a 224x224 frame and 20,615,520
    weights and biases: the paper's 4.0 GF and 20.6 M.  The 22 grouped
    3x3 convs hold 1,737,695,232 of them (44%)."""
    layers = REF.layers(FULL)
    assert ops.macs(layers) == 3_973_288_448
    grouped = [la for la in layers if la.name.endswith("_b")]
    assert len(grouped) == 22
    assert sum(la.macs for la in grouped) == 1_737_695_232
    assert sum(np.prod(la.w_shape) + la.d_out for la in layers) == 20_615_520


def test_layers_match_program_graph():
    api = get_cnn_api("regnety_4gf")
    params = jax.eval_shape(
        lambda: api.init(api.make_config(input_hw=(224, 224)), jax.random.key(0)))
    mine = {layer.name: layer.w_shape for layer in REF.layers(FULL)}
    assert mine == {name: p["w"].shape for name, p in params.items()}


def test_reference_matches_executor():
    """At the highest matmul precision on the CPU the two differ by
    float32 re-association alone."""
    params = ops.init(REF.layers(SMALL), jax.random.key(3))
    x = np.random.default_rng(0).standard_normal((2, 32, 32, 3), np.float32)
    want = jax.jit(functools.partial(REF.forward, cfg=SMALL))(params, x)
    api = get_cnn_api("regnety_4gf")
    cfg = api.make_config(input_hw=(32, 32), num_classes=10)
    with jax.default_matmul_precision("highest"):
        got = api.apply(params, x, cfg)
    assert check.frame_errors(np.asarray(got), np.asarray(want)).max() <= 1e-5


def test_served_path_matches_reference():
    """The served rate-matched path (Pallas kernels interpreted,
    micro-batch 2, 4 frames a call from a pool of 8) against the
    reference, under the cell's own limits; every grouped conv ran the
    grouped route (the executor asserts each record against the plan)."""
    cell = harness.load_cell("regnety_4gf.saturate")
    cell.config.update(input_hw=[32, 32], num_classes=10, microbatch=2)
    cell.mix.update(pool_frames=8, frames_per_call=4)
    served = harness.setup(cell, 2**31 + 19)
    verdict = harness.verify(served, harness.measure(served, 7, 0.2))
    assert verdict["correct"], verdict
    kp = served.serve_config.kernel_plan
    graph = served.api.graph(served.model_cfg)
    gpb = {n: kp[n].tile.bn * graph.spec(n).groups // graph.spec(n).d_out
           for n in graph.topo_order() if graph.spec(n).groups > 1}
    assert len(gpb) == 22 and not any(kp[n].tile.im2col for n in gpb)
    assert set(gpb.values()) == {2, 3, 17}


def _kpu(op, x, w, y):
    """HLO text of one Mosaic call with kpu_conv's operand layout."""
    def f32(s):
        return "f32[" + ",".join(map(str, s)) + "]"
    return (f"%{op} = {f32(y)}{{3,2,1,0}} custom-call({f32(x)} %a, {f32(w)} %b), "
            'custom_call_target="tpu_custom_call", '
            f"operand_layout_constraints={{{f32(x)}{{4,3,2,1,0}}, "
            f"{f32(w)}{{3,2,1,0}}}}, frontend_attributes={{kernel_metadata={{}}}}")


# s3b2_b as served: 8 frames of 14x14x512, 8 groups of 64; 16 columns
# stream for Wo = 14
GROUPED = ((8, 1, 16, 18, 512), (3, 3, 64, 512), (8, 14, 14, 512))
DENSE = ((8, 1, 16, 18, 512), (1, 1, 512, 512), (8, 14, 14, 512))
SE = ((8, 14, 14, 512), (8, 1, 512), (8, 14, 14, 512))


def test_kpu_conv_counts_a_grouped_calls_true_work():
    x, w, y = GROUPED
    kpu = load("kpu_conv")
    assert kpu.matches((x, w), y)
    assert kpu.flops((x, w), y) == 2 * 8 * 14 * 14 * 9 * 64 * 512
    call = mosaic_calls(_kpu("kpu_conv.s3b2_b.1", x, w, y))["kpu_conv.s3b2_b.1"]
    assert call.kind == "kpu_conv" and call.flops == kpu.flops((x, w), y)


def _context(with_grouped=True):
    """A made-up 1,000 ns window: a grouped kpu_conv call 100-400 ns, a
    dense one (a 1x1 projection) 400-500, an se_scale call 500-600, an
    XLA fusion 600-700."""
    ops_ = [
        ("/device:TPU:0", _kpu("kpu_conv.s3b2_b.1", *GROUPED), 100.0, 400.0),
        ("/device:TPU:0", _kpu("kpu_conv.s3b1_proj.1", *DENSE), 400.0, 500.0),
        ("/device:TPU:0", "se_scale.s3b2_scale.1", 500.0, 600.0),
        ("/device:TPU:0", "fusion.9", 600.0, 700.0),
    ]
    if not with_grouped:
        ops_ = ops_[1:]
    spans = [("bench_window", 0.0, 1000.0, {}), ("bench_serve", 0.0, 1000.0, {})]
    red = trace.Reduction(ops_, spans, PEAK)
    return harness.TraceContext(red, 8, 1, PEAK, lambda _: None)


def test_readers_pick_only_the_grouped_calls():
    ctx = _context()
    (x, w, y), kpu = GROUPED, load("kpu_conv")
    least = max(kpu.flops((x, w), y) / PEAK["flops"],
                kpu.bytes_moved((x, w), y, 4) / PEAK["hbm_bytes_per_s"])
    roof = metrics.load("group_conv_roofline").read(ctx)
    assert roof == pytest.approx(100 * least / 300e-9)
    # the grouped call alone, over 600 ns busy
    assert metrics.load("group_conv_share").read(ctx) == pytest.approx(50.0)


def test_readers_read_nothing_without_grouped_calls():
    """A trace of a model without grouped convs (or of a program without
    the route): both readers return None and do not raise."""
    ctx = _context(with_grouped=False)
    assert metrics.load("group_conv_roofline").read(ctx) is None
    assert metrics.load("group_conv_share").read(ctx) is None
