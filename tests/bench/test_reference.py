"""The benchmark's plain references agree with the served program's
executor, and count the published operations.

At 32x32 on the CPU: the reference at the highest matmul precision
against ``CNNApi.apply`` on the XLA path at the same precision, with the
benchmark's own weights.  At 224x224: the reference's multiply-adds per
frame equal the published counts.
"""

import jax
import numpy as np
import pytest

from bench.reference import load, ops
from repro.models.registry import get_cnn_api

SMALL = {"input_hw": [32, 32], "num_classes": 10}
FULL = {"input_hw": [224, 224], "num_classes": 1000}
# multiply-adds per 224x224 frame: ResNet-18 1.814 G (He et al. Table 1
# gives 1.8e9 FLOPs, counting multiply-adds), MobileNetV2 300 M
# (Sandler et al. Table 4), exact counts of the layer lists.
PUBLISHED_MACS = {"resnet18": 1_814_073_344, "mobilenet_v2": 300_774_272}


@pytest.mark.parametrize("family", sorted(PUBLISHED_MACS))
def test_reference_matches_executor(family):
    ref = load(family)
    params = ops.init(ref.layers(SMALL), jax.random.key(3))
    x = np.random.default_rng(0).standard_normal((2, 32, 32, 3), np.float32)
    want = ref.forward(params, x, SMALL)
    api = get_cnn_api(family)
    cfg = api.make_config(input_hw=(32, 32), num_classes=10)
    with jax.default_matmul_precision("highest"):
        got = api.apply(params, x, cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4 * float(np.abs(want).max()))


@pytest.mark.parametrize("family", sorted(PUBLISHED_MACS))
def test_flops_per_frame_are_published(family):
    ref = load(family)
    macs = ops.macs(ref.layers(FULL))
    assert macs == PUBLISHED_MACS[family]
    assert round(macs / 1e9, 3) == {"resnet18": 1.814, "mobilenet_v2": 0.301}[family]


@pytest.mark.parametrize("family", sorted(PUBLISHED_MACS))
def test_layers_match_program_graph(family):
    # every weighted node of the served graph, with its weight shape
    api = get_cnn_api(family)
    params = jax.eval_shape(
        lambda: api.init(api.make_config(**{"input_hw": (224, 224)}),
                         jax.random.key(0)))
    mine = {layer.name: layer.w_shape for layer in load(family).layers(FULL)}
    assert mine == {name: p["w"].shape for name, p in params.items()}


def test_three_pass_numerics_lie_between_one_pass_and_highest():
    """``HIGH`` splits each operand into bfloat16 high and low parts (the
    high part rounded to nearest even, as ``astype`` rounds) and keeps
    about 16 bits of it; one pass keeps 8."""
    x = jax.random.normal(jax.random.key(0), (64, 256), np.float32)
    w = jax.random.normal(jax.random.key(1), (256, 128), np.float32)
    hi, lo = ops._split(x)
    assert np.array_equal(np.asarray(hi), np.asarray(x.astype(jax.numpy.bfloat16)))
    rest = np.asarray(x) - np.asarray(hi, np.float32) - np.asarray(lo, np.float32)
    assert np.abs(rest).max() <= 2.0**-16 * np.abs(np.asarray(x)).max()
    p = {"w": w, "b": np.zeros(128, np.float32)}
    exact = np.asarray(ops.dense(x, p, ops.HIGHEST), np.float64)
    err = {name: np.abs(np.asarray(ops.dense(x, p, num)) - exact).max()
           for name, num in (("high", ops.HIGH), ("one_pass", ops.ONE_PASS))}
    assert 0 < err["high"] < err["one_pass"] / 100
