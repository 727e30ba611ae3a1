"""ResNet E2E: graph/apply parity, MAC ground truth, Pallas-vs-lax numerics."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.flops import graph_macs, graph_weight_count
from repro.core.graph import LayerGraph
from repro.models import cnn
from repro.models import resnet as rn
from repro.models.registry import get_cnn_api
from repro.models.topology import conv_spec

R18 = get_cnn_api("resnet18")


def test_resnet18_macs_match_hand_computed():
    """Total multiplies at 224x224 == the hand-computed ~1.81 GMACs
    (conv1 118.0M + stages 462.4/411.0/410.3/409.7M + fc 0.5M)."""
    g = rn.resnet18_graph()
    assert g.spec("conv1").total_macs == 112 * 112 * (7 * 7) * 3 * 64
    assert g.spec("fc").total_macs == 512 * 1000
    macs = graph_macs(g)
    assert abs(macs - 1.81e9) / 1.81e9 < 0.01
    assert macs == 1_814_073_344  # exact — the DSE plans on this number


def test_resnet_parameter_and_join_counts():
    g18, g34 = rn.resnet18_graph(), rn.resnet34_graph()
    assert len(g18.joins()) == 8 and len(g34.joins()) == 16
    assert abs(graph_weight_count(g18) / 1e6 - 11.7) < 0.1
    assert abs(graph_weight_count(g34) / 1e6 - 21.8) < 0.1
    assert abs(graph_macs(g34) - 3.66e9) / 3.66e9 < 0.01


def test_apply_full_resolution_finite():
    """ISSUE acceptance: ResNet-18 apply() end-to-end on a 224x224 batch
    (lax fallback), logits finite, and — because apply_graph runs with
    check=True — every layer's shape/MACs assert-matched the LayerGraph."""
    cfg = rn.ResNetConfig(depth=18)
    params = R18.init(cfg, jax.random.key(0))
    x = jax.random.normal(jax.random.key(1), (1, 224, 224, 3))
    logits = R18.apply(params, x, cfg)
    assert logits.shape == (1, 1000)
    assert bool(jnp.all(jnp.isfinite(logits)))


def test_apply_shape_drift_raises():
    """The executable net cannot silently drift from the DSE graph: a
    wrong head width is caught by the per-node shape check."""
    cfg = rn.ResNetConfig(depth=18, input_hw=(32, 32), num_classes=10)
    params = R18.init(cfg, jax.random.key(0))
    params["fc"] = {
        "w": jnp.zeros((512, 9)),
        "b": jnp.zeros((9,)),
    }
    x = jnp.zeros((1, 32, 32, 3))
    with pytest.raises(cnn.GraphExecutionError, match="fc"):
        R18.apply(params, x, cfg)


def test_apply_missing_params_raise():
    cfg = rn.ResNetConfig(depth=18, input_hw=(32, 32), num_classes=10)
    params = R18.init(cfg, jax.random.key(0))
    del params["l1b1_conv1"]
    with pytest.raises(cnn.GraphExecutionError, match="l1b1_conv1"):
        R18.apply(params, jnp.zeros((1, 32, 32, 3)), cfg)


def _small_block_graph():
    """A stem conv + one strided basic block (projection shortcut) — the
    smallest graph exercising conv, the residual join, and its relu."""
    g = LayerGraph()
    spec, hw = conv_spec("stem", "conv", 3, 16, (12, 12), 3, 1, act="relu")
    prev = g.add(spec)
    rn._basic_block(g, prev, "blk", 16, 32, hw, 2)
    return g


def test_kernel_backed_block_equals_lax():
    """Pallas KPU conv path == lax fallback on a small ResNet block —
    the DSE changes schedules, never math."""
    g = _small_block_graph()
    params = cnn.init_graph_params(g, jax.random.key(0))
    x = jax.random.normal(jax.random.key(1), (1, 12, 12, 3))
    base = cnn.apply_graph(params, x, g)
    kern = cnn.apply_graph(params, x, g, impls=cnn.kernel_impls())
    assert base.shape == (1, 6, 6, 32)
    np.testing.assert_allclose(np.asarray(kern), np.asarray(base),
                               rtol=2e-3, atol=2e-3)


def test_int8_quantization_close():
    """The paper's 8-bit datapath on ResNet: int8 weights preserve top-1
    agreement on most random inputs."""
    cfg = rn.ResNetConfig(depth=18, input_hw=(32, 32), num_classes=10)
    params = R18.init(cfg, jax.random.key(0))
    x = jax.random.normal(jax.random.key(1), (8, 32, 32, 3))
    ref = R18.apply(params, x, cfg)
    qp, scales = R18.quantize(params)
    got = R18.apply_int8(qp, scales, x, cfg)
    assert got.shape == ref.shape
    agree = float(jnp.mean(jnp.argmax(got, -1) == jnp.argmax(ref, -1)))
    assert agree >= 0.75, f"top-1 agreement {agree}"


def test_graph_params_cover_exactly_the_arith_nodes():
    cfg = rn.ResNetConfig(depth=34, input_hw=(64, 64), num_classes=10)
    g = cfg.graph()
    params = get_cnn_api("resnet34").init(cfg, jax.random.key(0))
    arith = {n for n in g.topo_order() if g.spec(n).kind in cnn.ARITH_KINDS}
    assert arith == set(params)
