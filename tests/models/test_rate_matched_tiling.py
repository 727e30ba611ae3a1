"""Plan threading: the executor provably follows the DAG DSE per node.

Covers the rate-matched tiling contract end to end:
  * analytic — ``GraphPlan.kernel_plan()`` derives every arithmetic
    node's tile from *that node's* (j, h) and decimation-adjusted
    demand, preserving the divisibility and continuous-flow invariants;
  * runtime — the tile each Pallas kernel actually executes (reported
    via the ops adapters' ``record`` hook) equals the planned tile on
    every node, and a tampered plan is detected;
  * equivalence — rate-matched and uniform kernel modes produce the
    same outputs (fp32 and int8): tiling choices change the schedule,
    never the math.
"""
import dataclasses
from fractions import Fraction as F

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import plan_graph
from repro.core.dse import NON_ARITH_KINDS
from repro.core.hw_specs import TPU_V5E
from repro.core.tpu_tiles import conv_block_frames, conv_geometry, mxu_width
from repro.kernels.common import conv_taps, phase_split
from repro.kernels.kpu_conv.kpu_conv import kpu_conv_p
from repro.models import cnn
from repro.models.registry import get_cnn_api

FAMILIES = ("resnet18", "mobilenet_v2")
RATE = F(3)  # 3 features/clock at d_in=3 == 1 pixel/clock


def _setup(family):
    api = get_cnn_api(family)
    cfg = api.make_config(input_hw=(32, 32), num_classes=10)
    return api, cfg


@pytest.mark.parametrize("family", FAMILIES)
def test_kernel_plan_tiles_follow_each_nodes_dse_choice(family):
    """Analytic half: tile floors come from (j, h); growth never breaks
    divisibility or Eq. 9 (capacity >= the node's own demand)."""
    api, cfg = _setup(family)
    graph = api.graph(cfg)
    gp = plan_graph(graph, RATE)
    kp = gp.kernel_plan()
    assert list(kp) == graph.topo_order()
    n_tiles = 0
    for name, node in kp.items():
        spec = graph.spec(name)
        impl = gp.impls[name]
        assert node.demand == impl.demand  # decimation-adjusted, per node
        if spec.kind in NON_ARITH_KINDS:
            assert node.tile is None
            continue
        n_tiles += 1
        t = node.tile
        assert spec.d_in % t.bk == 0
        assert t.bk >= min(impl.j, spec.d_in)
        if spec.kind == "dwconv":
            assert t.bn == 1
            continue
        assert spec.d_out % t.bn == 0
        assert t.bn >= max(1, spec.d_out // impl.h)
        # continuous flow survives the MXU-alignment growth
        r_phase = impl.demand / impl.p_raw
        assert F(t.bk, max(1, spec.d_out // t.bn)) >= r_phase
    assert n_tiles > 10  # the whole conv stack is planned, not a corner


def test_plans_differ_across_nodes_no_global_rate():
    """The point of the paper: per-node demand differs, so tiles differ —
    the rate-matched path is not one global configuration in disguise."""
    api, cfg = _setup("resnet18")
    kp = api.plan(cfg, RATE)
    demands = {p.demand for p in kp.values() if p.has_kernel}
    tiles = {(p.tile.bk, p.tile.bn) for p in kp.values() if p.has_kernel}
    assert len(demands) > 1
    assert len(tiles) > 1


@pytest.mark.parametrize("family", FAMILIES)
def test_executed_tile_matches_plan_on_every_node(family):
    """Runtime half: run the real Pallas kernels (interpret mode) under a
    plan; every arithmetic node must report exactly the planned tile
    (apply_graph raises otherwise), and the report must cover all of
    them."""
    api, cfg = _setup(family)
    graph = api.graph(cfg)
    kp = api.plan(cfg, RATE)
    params = api.init(cfg, jax.random.key(0))
    x = jax.random.normal(jax.random.key(1), (1, 32, 32, 3))
    executed = {}
    y = cnn.apply_graph(params, x, graph, plan=kp, executed=executed)
    assert y.shape == (1, 10)
    planned = {n for n, p in kp.items() if p.has_kernel}
    assert set(executed) == planned
    for name in planned:
        t = kp[name].tile
        assert executed[name]["bk"] == t.bk
        assert executed[name]["bn"] == t.bn


def test_tampered_plan_is_detected():
    """If execution disagrees with the plan (here: kernels pinned to the
    real plan, but a tampered table passed as the contract), the
    per-node assertion must fire."""
    api, cfg = _setup("resnet18")
    graph = api.graph(cfg)
    kp = api.plan(cfg, RATE)
    params = api.init(cfg, jax.random.key(0))
    x = jax.random.normal(jax.random.key(1), (1, 32, 32, 3))
    victim = "l1b1_conv1"
    t = kp[victim].tile
    bad_tile = dataclasses.replace(t, bk=max(1, t.bk // 2))
    tampered = dict(kp)
    tampered[victim] = dataclasses.replace(kp[victim], tile=bad_tile)
    executed = {}
    real_impls = cnn.kernel_impls(plan=kp, executed=executed)
    with pytest.raises(cnn.GraphExecutionError, match=victim):
        cnn.apply_graph(params, x, graph, impls=real_impls, plan=tampered,
                        executed=executed)


def test_frames_per_step_other_than_the_batch_pinned_plan_is_detected():
    """A whole-frame conv must hold the frames per grid step that its
    batch-pinned bm covers (the plan's multi-pixel P): kernels run with
    one frame a step against that plan must trip the per-node check."""
    api, cfg = _setup("resnet18")
    graph = api.graph(cfg)
    kp = plan_graph(graph, RATE).kernel_plan(batch=2)
    params = api.init(cfg, jax.random.key(0))
    x = jax.random.normal(jax.random.key(1), (2, 32, 32, 3))
    victim = "l3b2_conv1"  # 2x2 output: bm covers both frames
    executed = {}
    cnn.apply_graph(params, x, graph, plan=kp, executed=executed)
    assert executed[victim]["frames"] == 2
    spec = graph.spec(victim)
    one_frame = dict(kp)
    one_frame[victim] = dataclasses.replace(
        kp[victim],
        tile=dataclasses.replace(kp[victim].tile, bm=spec.out_hw[0] * spec.out_hw[1]),
    )
    executed = {}
    impls = cnn.kernel_impls(plan=one_frame, executed=executed)
    with pytest.raises(cnn.GraphExecutionError, match=f"{victim}.*frames"):
        cnn.apply_graph(params, x, graph, impls=impls, plan=kp,
                        executed=executed)


def test_row_width_other_than_the_sublane_rule_is_detected():
    """A whole-frame conv must stream its output width rounded up to the
    sublane tile: a kernel run at the true, unaligned width (and saying
    so in its record) must trip the per-node check."""
    api, cfg = _setup("resnet18")
    graph = api.graph(cfg)
    kp = api.plan(cfg, RATE)
    params = api.init(cfg, jax.random.key(0))
    x = jax.random.normal(jax.random.key(1), (1, 32, 32, 3))
    victim = "l2b2_conv1"  # 4x4 output: rows of 4 stream as 8
    executed = {}
    cnn.apply_graph(params, x, graph, plan=kp, executed=executed)
    assert executed[victim]["wo_mxu"] == 8
    t = kp[victim].tile

    def true_width(x, w, stride):
        geo = conv_geometry(x.shape[1:3], w.shape[:2], stride)
        executed[victim] = dict(bk=t.bk, bn=t.bn, d_in=x.shape[-1],
                                d_out=w.shape[-1], frames=1,
                                wo_mxu=geo.wo_mxu)
        return kpu_conv_p(phase_split(x, geo), w,
                          taps=conv_taps(geo, w.shape[:2]),
                          out_hw=geo.out_hw, bci=t.bk, bco=t.bn)

    with pytest.raises(cnn.GraphExecutionError, match=f"{victim}.*wo_mxu=8"):
        cnn.apply_graph(params, x, graph, plan=kp,
                        overrides={victim: true_width}, executed=executed)


@pytest.mark.parametrize("family", FAMILIES)
def test_rate_matched_equals_uniform_fp32_and_int8(family):
    """Equivalence: per-layer tiling follows the DSE but the arithmetic
    is unchanged — rate-matched and uniform kernel modes agree, in fp32
    and through the int8 weight path."""
    api, cfg = _setup(family)
    graph = api.graph(cfg)
    kp = api.plan(cfg, RATE)
    params = api.init(cfg, jax.random.key(0))
    x = jax.random.normal(jax.random.key(1), (1, 32, 32, 3))

    rm = api.apply(params, x, cfg, plan=kp)
    uni = api.apply(params, x, cfg, conv_impls=cnn.kernel_impls())
    np.testing.assert_allclose(np.asarray(rm), np.asarray(uni),
                               rtol=2e-4, atol=2e-4)
    assert bool(jnp.all(jnp.isfinite(rm)))

    q, scales = api.quantize(params)
    rm8 = api.apply_int8(q, scales, x, cfg, plan=kp)
    uni8 = cnn.apply_int8(q, scales, x, graph, impls=cnn.kernel_impls())
    np.testing.assert_allclose(np.asarray(rm8), np.asarray(uni8),
                               rtol=2e-4, atol=2e-4)


def test_ref11_plans_lower_without_feasibility_claim():
    """[11]'s (j, h) are bookkeeping decoupled from its capacity formula
    (and can be infeasible outright); kernel_plan must still lower every
    node best-effort instead of tripping the Eq.-9 consistency guard."""
    api, cfg = _setup("resnet18")
    graph = api.graph(cfg)
    kp = plan_graph(graph, RATE, scheme="ref11").kernel_plan()
    for name, node in kp.items():
        spec = graph.spec(name)
        if spec.kind in NON_ARITH_KINDS:
            continue
        assert spec.d_in % node.tile.bk == 0
        if spec.kind != "dwconv":
            assert spec.d_out % node.tile.bn == 0


def _grouped_record(spec, node_plan, batch):
    """What the grouped route reports for ``spec`` under its plan."""
    t = node_plan.tile
    return dict(
        bk=t.bk, bn=t.bn, d_in=spec.d_in, d_out=spec.d_out,
        frames=conv_block_frames(batch, spec.out_hw[0] * spec.out_hw[1], t.bm),
        wo_mxu=mxu_width(spec.out_hw[1], 4, TPU_V5E),
        groups=spec.groups, gpb=t.bn * spec.groups // spec.d_out,
    )


@pytest.mark.parametrize(
    "tamper",
    [{"gpb": 1}, {"gpb": 8}, {"groups": 4}, {"groups": None, "gpb": None}],
    ids=["fewer-groups-a-step", "more-groups-a-step", "other-groups",
         "dense-route"],
)
def test_grouped_route_other_than_planned_is_detected(tamper):
    """A grouped conv must report the grouped route with the plan's
    groups and groups per grid step: a record with other ``groups`` or
    ``gpb`` (or none, as the dense route gives) trips the per-node
    check; the honest record passes."""
    api = get_cnn_api("regnety_4gf")
    graph = api.graph(api.make_config(input_hw=(32, 32), num_classes=10))
    kp = plan_graph(graph, RATE).kernel_plan(batch=2)
    victim = "s3b2_b"  # 8 groups of 64 channels, 2 a step
    spec = graph.spec(victim)
    honest = _grouped_record(spec, kp[victim], 2)
    assert (honest["groups"], honest["gpb"]) == (8, 2)
    cnn._check_planned_tile(spec, kp[victim], honest, 4)
    with pytest.raises(cnn.GraphExecutionError, match=f"{victim}.*groups"):
        cnn._check_planned_tile(spec, kp[victim], {**honest, **tamper}, 4)
