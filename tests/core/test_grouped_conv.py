"""A grouped conv through the rate model, the DSE, the resource model
and the tile rule: ``LayerSpec.groups`` on the 'conv' kind."""
from fractions import Fraction as F

import pytest

from repro.core import LayerSpec, select_ours, select_ref11
from repro.core.flops import graph_macs, graph_weight_count
from repro.core.graph import LayerGraph, plan_graph
from repro.core.resource_model import estimate_layer
from repro.core.tpu_tiles import (
    group_blocks,
    groups_per_block,
    select_tile_for_impl,
)
from repro.kernels.kpu_conv.kpu_conv import groups_per_dot


def _conv(groups, d=128, hw=16, s=1):
    return LayerSpec(name="gc", kind="conv", d_in=d, d_out=d, in_hw=(hw, hw),
                     out_hw=(hw // s, hw // s), kernel=(3, 3), stride=(s, s),
                     groups=groups)


@pytest.mark.parametrize("groups", [2, 4, 8])
def test_work_and_multipliers_are_the_dense_counts_over_g(groups):
    """At a rate both can meet exactly, a grouped conv needs 1/G of the
    dense conv's multiply-adds, weights and multipliers, at the dense
    conv's capacity."""
    dense, grouped = _conv(1), _conv(groups)
    assert grouped.macs_per_pixel * groups == dense.macs_per_pixel
    assert grouped.total_macs * groups == dense.total_macs
    assert (grouped.weight_count - grouped.d_out) * groups == (
        dense.weight_count - dense.d_out)
    r = F(1)
    a, b = select_ours(dense, r), select_ours(grouped, r)
    assert b.mults * groups == a.mults
    assert b.capacity == a.capacity == r  # BestRate meets r exactly
    assert b.feasible
    # a unit aggregates inputs of one group only
    assert b.j <= grouped.group_in and grouped.group_in % b.j == 0
    assert (grouped.d_out // groups) % b.h == 0
    assert b.configs == b.h * grouped.group_in // b.j


@pytest.mark.parametrize("r", [F(1, 8), F(1, 3), F(2), F(5, 2), F(64)])
def test_grouped_dse_is_feasible_at_every_rate(r):
    """Continuous flow (capacity >= demand) at rates above and below one
    pixel a clock, under both schemes' bookkeeping."""
    spec = _conv(8)
    ours = select_ours(spec, r)
    assert ours.feasible and ours.capacity == ours.p_raw * 8 * F(ours.j, ours.h)
    ref = select_ref11(spec, r)
    assert ref.units >= 8 * ref.p  # at least one KPU per group and phase


def test_resource_model_reads_the_grouped_multipliers():
    dense, grouped = select_ours(_conv(1), F(1)), select_ours(_conv(8), F(1))
    assert estimate_layer(grouped).dsp < estimate_layer(dense).dsp


def test_groups_belong_to_conv_and_divide_its_channels():
    with pytest.raises(ValueError, match="groups"):
        LayerSpec(name="x", kind="pointwise", d_in=8, d_out=8, in_hw=(4, 4),
                  out_hw=(4, 4), groups=2)
    with pytest.raises(ValueError, match="groups"):
        _conv(3)  # 128 channels do not split into 3 groups
    # a dense layer prints as it always has
    assert "groups" not in repr(_conv(1))


def test_graph_counts_the_grouped_work():
    g = LayerGraph()
    g.add(_conv(4))
    assert graph_macs(g) == 16 * 16 * 9 * 32 * 128
    assert graph_weight_count(g) == 9 * 32 * 128 + 128


def test_group_blocks_hold_whole_lane_tiles():
    assert group_blocks(2, 64, 64, 128) == [2]
    assert group_blocks(3, 64, 64, 128) == [3]
    assert group_blocks(8, 64, 64, 128) == [2, 4, 8]
    assert group_blocks(17, 64, 64, 128) == [17]
    assert group_blocks(4, 128, 128, 128) == [1, 2, 4]
    assert groups_per_dot(8, 64) == 2
    assert groups_per_dot(17, 64) == 2
    assert groups_per_dot(4, 128) == 1
    assert groups_per_dot(3, 4) == 3


def test_groups_per_block_narrows_to_fit():
    def fits(gpb):
        return gpb <= 4

    assert groups_per_block(8, 64, 64, 512, fits, 128) == 4
    assert groups_per_block(8, 64, 64, 1, fits, 128) == 2
    assert groups_per_block(8, 64, 64, 512, lambda g: True, 128) == 8
    # nothing fits: the fewest legal
    assert groups_per_block(8, 64, 64, 512, lambda g: False, 128) == 2


@pytest.mark.parametrize("fraction", [0.5, 0.02])
def test_grouped_tile_holds_whole_groups_and_never_im2col(fraction):
    """The plan's tile holds whole groups; a tight VMEM budget narrows
    it to fewer groups a step, never to the im2col route."""
    spec = _conv(8, d=512, hw=56)
    g = LayerGraph()
    g.add(spec)
    impl = plan_graph(g, F(512)).impls["gc"]
    t = select_tile_for_impl(impl, batch=8, vmem_fraction=fraction)
    gpb = t.bn // 64
    assert not t.im2col and t.grid_k == 1
    assert t.bk == gpb * 64 and t.grid_n == 8 // gpb
    assert gpb in group_blocks(8, 64, 64, 128)
    if fraction < 0.5:
        assert gpb == 2  # narrowed below the DSE's floor to fit
    else:
        # Eq. 9: the tile absorbs at least what the DSE's (j, h) absorb
        assert F(spec.d_in * t.bn, spec.d_out) * impl.p_raw >= impl.capacity
