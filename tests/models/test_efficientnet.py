"""EfficientNet-B0 on the served path against the benchmark's plain
reference (``bench/reference/efficientnet_b0.py``), at 32x32 and 10
classes with the benchmark's seeded weights.

The program runs in float32 at the ``highest`` matmul precision, as the
benchmark's configuration states; each comparison is the benchmark's own
frame error, max |program - reference| / max |reference|
(``bench/check.frame_errors``).  For scale: at 224x224 the reference
computed at ``high`` (three bfloat16 passes, the benchmark's control)
reads about 4e-6 against the reference at ``highest``.
"""
import dataclasses
import functools
import hashlib
import json
from fractions import Fraction as F

import jax
import numpy as np
import pytest

from bench import check
from bench.reference import load, ops
from repro.core.stage_partition import stream_buffers
from repro.models import cnn
from repro.models.registry import get_cnn_api
from repro.serving.cnn_stream import CNNStreamEngine
from repro.serving.config import ServeConfig

SMALL = {"input_hw": [32, 32], "num_classes": 10}
RATE = F(3)
# The XLA path runs the reference's operations in another grouping (the
# executor's einsum and conv against the reference's tensordot and conv),
# so the two may differ by float32 re-association alone: ~1e-7 relative
# per layer over 65 layers.
XLA_TOL = 1e-5
# The Pallas kernels (interpret mode on the CPU) accumulate each matmul
# in VMEM-sized blocks and each depthwise conv tap by tap: again float32
# re-association, the same bound.
PALLAS_TOL = 1e-5


@pytest.fixture(scope="module")
def case():
    ref = load("efficientnet_b0")
    params = ops.init(ref.layers(SMALL), jax.random.key(3))
    x = np.random.default_rng(0).standard_normal((2, 32, 32, 3), np.float32)
    want = np.asarray(jax.jit(functools.partial(ref.forward, cfg=SMALL))(params, x))
    api = get_cnn_api("efficientnet_b0")
    cfg = api.make_config(input_hw=(32, 32), num_classes=10)
    return api, cfg, params, x, want


def _err(got, want):
    return float(check.frame_errors(np.asarray(got), want).max())


def test_apply_xla_matches_reference(case):
    api, cfg, params, x, want = case
    with jax.default_matmul_precision("highest"):
        got = api.apply(params, x, cfg)
    assert _err(got, want) <= XLA_TOL


def test_apply_kernel_plan_matches_reference(case):
    """The rate-matched plan: every node on its Pallas kernel (dw_conv at
    k=3 and 5, fcu_matmul, se_scale), each executed tile asserted equal
    to the plan's."""
    api, cfg, params, x, want = case
    kp = api.plan(cfg, RATE)
    assert {kp[n].kind for n in kp if kp[n].has_kernel} == {
        "conv", "dwconv", "pointwise", "dense", "scale"}
    with jax.default_matmul_precision("highest"):
        got = api.apply(params, x, cfg, plan=kp)
    assert _err(got, want) <= PALLAS_TOL


def test_scale_join_takes_a_node_override(case):
    """A 'scale' node runs a kernel, so it takes a node-keyed override like
    the arithmetic nodes; the gate's pooling, which has none, does not."""
    api, cfg, params, x, want = case
    seen = []

    def scale(t, gate):
        seen.append(gate.shape)
        return t * gate[:, None, None, :]

    with jax.default_matmul_precision("highest"):
        got = api.apply(params, x, cfg, overrides={"b2_scale": scale})
    assert seen == [(2, 96)]
    assert _err(got, want) <= XLA_TOL
    with pytest.raises(cnn.GraphExecutionError, match="no kernel"):
        api.apply(params, x, cfg, overrides={"b2_se_gap": scale})


@pytest.mark.parametrize("n_stages", [1, 2])
def test_serve_matches_reference(case, n_stages):
    """``CNNApi.serve`` with the batch-pinned kernel plan, as the
    benchmark serves it."""
    api, cfg, params, x, want = case
    kp = api.partition(cfg, RATE, n_stages).kernel_plan(batch=2)
    with jax.default_matmul_precision("highest"):
        out, report = api.serve(
            params, list(x), cfg, input_rate=RATE, n_stages=n_stages,
            config=ServeConfig(microbatch=2, kernel_plan=kp))
    assert report.completed == 2
    assert _err(out, want) <= PALLAS_TOL


def test_engine_with_a_cut_across_a_gate(case):
    """Two stages cut between ``b5_se_expand`` and ``b5_scale``: both the
    trunk edge (``b5_dw``) and the gate edge (``b5_se_expand``) cross."""
    api, cfg, params, x, want = case
    graph = api.graph(cfg)
    plan = api.partition(cfg, RATE, 2)
    order = plan.stage_plan.order
    k = order.index("b5_scale")
    sp = dataclasses.replace(plan.stage_plan, boundaries=(0, k, len(order)))
    plan = dataclasses.replace(plan, stage_plan=sp,
                               stream_bufs=stream_buffers(plan, sp))
    crossing = {(b.src, b.dst) for b in plan.stream_bufs}
    assert {("b5_dw", "b5_scale"), ("b5_se_expand", "b5_scale")} <= crossing
    gate = plan.buffer_for("b5_scale", "b5_se_expand")
    edge = next(b for b in plan.stream_bufs if b.src == "b5_se_expand")
    assert edge.q == plan.timing["b5_se_expand"].q_out  # one gate a frame
    h, w = graph.spec("b5_scale").in_hw
    assert edge.q * h * w == plan.timing["b5_scale"].q_in
    assert gate.bound_pixels >= 1
    eng = CNNStreamEngine(graph, params, plan, ServeConfig(
        microbatch=2, kernel_plan=plan.kernel_plan(batch=2), dtype=cfg.dtype,
        arrival=F(1)))
    eng.submit_all(x)
    with jax.default_matmul_precision("highest"):
        eng.run()
    assert _err(eng.outputs(), want) <= PALLAS_TOL


def _graph_digest(graph) -> str:
    rows = [[n, repr(graph.spec(n)), graph.preds(n)] for n in graph.topo_order()]
    return hashlib.sha256(json.dumps(rows).encode()).hexdigest()


def test_mobilenet_v2_graph_is_unchanged_by_the_shared_body():
    """MobileNetV2 and EfficientNet-B0 share one block walk
    (``mobilenet.inverted_residual_body``).  The digest of MobileNetV2's
    nodes, ``LayerSpec``s and edges at 224x224 is the one its own walk
    gave before the walk was shared."""
    api = get_cnn_api("mobilenet_v2")
    assert _graph_digest(api.graph(api.make_config())) == (
        "10db5f8f8c087968d1d6ebe27e1d687593502de4caddd18453227b289ca5e6b6")


# sha256 of each plan's per-node (j, h), timing, join buffers, batch-8
# kernel plan and, cut into stages, its partition and stream buffers, as
# the planner gave them before the 'scale' join and the producer-rate
# pricing of join and cut edges were added.
PLAN_DIGESTS = {
    ("resnet18", 224, 1): "a6842fcd6be648be3bda58315327b5f7c78e9ea177ab1d6d51cc10f64094ea03",
    ("resnet18", 224, 2): "f472df85bddd6e46d8298bd87871640385fb64b57aab064cc84df4551d8d6166",
    ("resnet18", 224, 4): "21e4fec1cefe70bef5004527ff3183c8b0d32bc9999bc40ea1792fdd8d5a8f53",
    ("resnet18", 32, 1): "ba8bb2664d0fea04342674cb5c251040b3a5295dd8d77c60057332e5b46c9068",
    ("resnet18", 32, 2): "8f96d826c877e2c235f2bd5c41727e0fada6d91b0327def59c7df57ca0bf89a7",
    ("resnet18", 32, 4): "d882f0630f52bfd5ca941e464d04da1c703561e8645cc86a8cf44592421a98ff",
    ("mobilenet_v2", 224, 1): "15cc1652850624d15f78089e3606af5f7fdcfce366d00181f897a6872ca6749a",
    ("mobilenet_v2", 224, 2): "d4d2baba04cdb25b467877643e655b34a3f1176109d74500bba229ac62397279",
    ("mobilenet_v2", 224, 4): "aad32b292f7ed7186a68f0778ed1041c85a05db55efef78c3fbc858ec2e76602",
    ("mobilenet_v2", 32, 1): "2be779e5ff1dab24add739d1a8f4b47ab6287f6e830b3ca857afd46b8af99cdd",
    ("mobilenet_v2", 32, 2): "e51dd717883a447d88eba4aeea3d3c16c7182bde7bce65c0dd6edc988bdf6ec8",
    ("mobilenet_v2", 32, 4): "a2eee95204d191fd7cdafd8fd83eb9ebf925514c6ab04990d89382e24528e866",
}


@pytest.mark.parametrize("family,hw,n_stages", sorted(PLAN_DIGESTS))
def test_benchmarked_plans_are_unchanged(family, hw, n_stages):
    """ResNet-18's and MobileNetV2's plans, buffers, tiles and partitions
    at the benchmark's input rate are the ones the planner gave them
    before EfficientNet-B0 shared its code."""
    api = get_cnn_api(family)
    gp = api.partition(api.make_config(input_hw=(hw, hw), num_classes=1000),
                       RATE, n_stages)
    parts = [list(gp.impls.items()), sorted(gp.timing.items()), gp.buffers,
             list(gp.kernel_plan(batch=8).items())]
    if n_stages > 1:
        parts += [gp.stage_plan, gp.stream_bufs]
    digest = hashlib.sha256(repr(parts).encode()).hexdigest()
    assert digest == PLAN_DIGESTS[family, hw, n_stages]


def test_block_shapes_follow_table_1():
    api = get_cnn_api("efficientnet_b0")
    g = api.graph(api.make_config())
    blocks = sorted({n.split("_")[0] for n in g.topo_order()
                     if n.startswith("b") and n[1].isdigit()},
                    key=lambda b: int(b[1:]))
    assert len(blocks) == 16
    assert [g.spec(f"{b}_dw").kernel[0] for b in blocks].count(5) == 9
    assert g.spec("b1_se_reduce").d_out == 8  # 0.25 of the block's 32 inputs
    assert g.spec("b2_se_reduce").d_out == 4  # of 16, not of the expanded 96
    assert g.spec("b2_se_expand").activation == "sigmoid"
    assert g.spec("b2_se_reduce").activation == "swish"
    assert g.spec("b2_project").activation == "none"
    assert g.preds("b2_scale") == ["b2_dw", "b2_se_expand"]
    assert g.spec("conv_last").d_out == 1280
