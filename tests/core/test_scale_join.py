"""The 'scale' join (squeeze-and-excitation): shape checks, rate and
offset propagation, the whole-frame trunk buffer, the discrete-event run,
and its kernel tile."""
from fractions import Fraction as F

import pytest

from repro.core import GraphError, LayerGraph, LayerSpec, plan_graph, propagate_graph
from repro.core.schedule import simulate_graph
from repro.core.tpu_tiles import SCALE_BLOCK_BYTES, vmem_budget
from repro.core.hw_specs import TPU_V5E
from repro.models.efficientnet import efficientnet_b0_graph
from repro.models.topology import dense_spec, gap_spec, scale_spec

SWEEP = [F(6, 1), F(3, 1), F(3, 2), F(3, 4), F(3, 8), F(3, 16), F(3, 32)]


def _se_block(d=16, hw=(8, 8), gate_d=None):
    """trunk pointwise -> gap -> dense -> dense -> scale(trunk, gate)."""
    g = LayerGraph()
    g.add(LayerSpec(name="trunk", kind="pointwise", d_in=d, d_out=d,
                    in_hw=hw, out_hw=hw))
    g.add(gap_spec("pool", d, hw), ["trunk"])
    g.add(dense_spec("reduce", d, 4, act="swish"), ["pool"])
    g.add(dense_spec("expand", 4, gate_d or d, act="sigmoid"), ["reduce"])
    return g


def test_scale_accepts_a_trunk_beside_a_one_pixel_gate():
    g = _se_block()
    g.add(scale_spec("scale", 16, (8, 8)), ["trunk", "expand"])
    assert g.joins() == ["scale"]
    demands, out = propagate_graph(g, F(16))
    assert out["scale"].pixels_per_clock == out["trunk"].pixels_per_clock
    assert out["expand"].pixels_per_clock * 64 == out["trunk"].pixels_per_clock


def test_scale_refuses_bad_operands():
    g = _se_block(gate_d=8)
    with pytest.raises(GraphError, match="equal operand channels"):
        g.add(scale_spec("scale", 16, (8, 8)), ["trunk", "expand"])
    g = _se_block()
    with pytest.raises(GraphError, match="1x1"):  # the trunk as the gate
        g.add(scale_spec("scale", 16, (8, 8)), ["trunk", "trunk"])
    with pytest.raises(GraphError, match=r"\[trunk, gate\]"):
        g.add(scale_spec("scale", 16, (8, 8)), ["trunk"])
    with pytest.raises(GraphError, match="trunk"):
        g.add(scale_spec("scale", 16, (4, 4)), ["trunk", "expand"])
    with pytest.raises(GraphError, match="d_out == d_in"):
        g.add(LayerSpec(name="scale", kind="scale", d_in=16, d_out=8,
                        in_hw=(8, 8), out_hw=(8, 8)), ["trunk", "expand"])


def test_scale_refuses_a_gate_not_once_a_frame():
    """A 1x1 stream at the trunk's own pixel rate (here a second source)
    is not a gate: the join needs one gate per frame of the trunk."""
    g = LayerGraph()
    g.add(LayerSpec(name="trunk", kind="pointwise", d_in=8, d_out=8,
                    in_hw=(8, 8), out_hw=(8, 8)))
    g.add(dense_spec("other", 8, 8))
    g.add(scale_spec("scale", 8, (8, 8)), ["trunk", "other"])
    with pytest.raises(GraphError, match="not one per frame"):
        propagate_graph(g, F(8))


@pytest.mark.parametrize("rate", SWEEP)
def test_efficientnet_b0_graph_continuous_flow(rate):
    """Three frames through every block's gate: no stalls, and every FIFO
    (the whole-frame trunk FIFOs, the gates, the residual skews) within
    its analytic bound."""
    plan = plan_graph(efficientnet_b0_graph((32, 32), 10), rate)
    assert plan.continuous_flow
    res = simulate_graph(plan, 3 * 32 * 32)
    assert res.stall_free, res.stalled_nodes
    assert res.within_bounds, [
        (o.join, o.src, o.max_pixels, o.bound_pixels)
        for o in res.occupancy if not o.within_bound]
    trunk = {o.join: o for o in res.occupancy if o.src.endswith("_dw")}
    # the trunk parks at least one whole frame: b3 runs at 8x8 here
    assert trunk["b3_scale"].max_pixels >= 64
    assert res.traces["fc"].busy_cycles > 0  # frames reached the classifier


def test_b3_trunk_buffers_a_whole_frame():
    """b3's depthwise output, 56x56x144 at 224x224: its FIFO on the scale
    join holds the frame's 3,136 pixels plus the gate path's latency
    (gap, the two dense layers) at the stream's rate."""
    plan = plan_graph(efficientnet_b0_graph(), F(3))
    trunk = plan.buffer_for("b3_scale", "b3_dw")
    gate = plan.buffer_for("b3_scale", "b3_se_expand")
    t = plan.timing
    assert trunk.d == 144 and trunk.q == F(1, 16)
    latency = (t["b3_se_expand"].offset - t["b3_dw"].offset) * trunk.q
    assert trunk.bound_pixels == 56 * 56 - 1 + int(latency) + 1 == 3245
    assert gate.d == 144 and gate.q == trunk.q / (56 * 56)
    assert gate.bound_pixels == 2
    # the join waits for its gate: its offset is the gate's, a frame on
    assert t["b3_scale"].offset == (
        t["b3_se_expand"].offset + F(56 * 56 - 1) / trunk.q
        + t["b3_scale"].pass_cycles)


def test_scale_tile_streams_row_blocks():
    plan = plan_graph(efficientnet_b0_graph(), F(3))
    assert plan.impls["b1_scale"].mults == 0  # no (j, h): one multiply a feature
    kp = plan.kernel_plan(batch=8)
    tile = kp["b1_scale"].tile  # 112x112x32
    assert (tile.bk, tile.bn, tile.bm) == (32, 1, 28 * 112)
    assert tile.grid_m == 4
    assert tile.vmem_bytes <= vmem_budget(TPU_V5E)
    assert 2 * SCALE_BLOCK_BYTES * 2 >= tile.vmem_bytes - 2 * 8 * 128 * 4
    # 7x7x1152: the floor j = 2 grows to the whole frame, one block
    assert (kp["b16_scale"].tile.bk, kp["b16_scale"].tile.bm) == (1152, 49)
    assert not kp["b1_se_gap"].has_kernel
