"""TPU-side core: tile selection, stage partitioning, HLO parsing."""
from fractions import Fraction as F

import pytest
from hypothesis import given, settings, strategies as st

from repro.core.tpu_tiles import select_tile
from repro.core.stage_partition import (
    allocate_chips, partition_blocks, partition_min_bottleneck, service_rates,
)
from repro.core.hlo_analysis import collective_bytes, roofline_terms


channels = st.sampled_from([64, 128, 256, 512, 1024, 4096, 6144])


@given(channels, channels, st.sampled_from([128, 1024, 8192]))
@settings(max_examples=50, deadline=None)
def test_tile_divisibility_and_vmem(d_in, d_out, m):
    t = select_tile(m, d_in, d_out)
    assert d_in % t.bk == 0            # Eq. (7) analogue
    assert d_out % t.bn == 0           # Eq. (8) analogue
    assert t.vmem_bytes <= 64 * 1024**2
    assert t.grid_k == d_in // t.bk


def test_tile_prefers_mxu_alignment():
    t = select_tile(8192, 4096, 4096)
    assert t.mxu_aligned
    assert t.bk % 128 == 0 and t.bn % 128 == 0


def test_tile_rate_constraint():
    """Low stream rate => small j/h tile is allowed & selected feasibly."""
    t = select_tile(1024, 512, 512, rate=F(1, 4))
    assert F(t.bk, max(1, 512 // t.bn)) >= F(1, 4)


# ---------------------------------------------------------------------------
# stage partitioning
# ---------------------------------------------------------------------------

def test_partition_balances_uniform():
    plan = partition_min_bottleneck([1.0] * 16, 4)
    assert plan.stage_cost == (4.0, 4.0, 4.0, 4.0)
    assert plan.balance == 1.0


def test_partition_respects_rate_drop():
    """A network whose cost halves midway (pooling!) gets more layers per
    stage downstream — the paper's rate-awareness at stage level."""
    costs = [8.0] * 4 + [1.0] * 8
    plan = partition_min_bottleneck(costs, 4)
    sizes = [plan.boundaries[i + 1] - plan.boundaries[i] for i in range(4)]
    assert sizes[0] < sizes[-1]
    # contiguous optimum here is 16 (the 8s are adjacent); DP must find it
    assert plan.bottleneck <= 16.0


@given(st.lists(st.floats(min_value=0.1, max_value=100), min_size=4, max_size=40),
       st.integers(min_value=1, max_value=4))
@settings(max_examples=50, deadline=None)
def test_partition_optimality_vs_even_split(costs, s):
    if s > len(costs):
        return
    plan = partition_min_bottleneck(costs, s)
    # DP must beat (or match) the naive even-count split
    n = len(costs)
    bounds = [round(i * n / s) for i in range(s + 1)]
    naive = max(sum(costs[bounds[i]:bounds[i + 1]]) for i in range(s)
                if bounds[i + 1] > bounds[i])
    assert plan.bottleneck <= naive + 1e-9


def test_partition_blocks_divisibility():
    plan = partition_blocks([1.0] * 24, 4, block=4)
    assert all(b % 4 == 0 for b in plan.boundaries)


def test_allocate_chips_proportional():
    chips = allocate_chips([100.0, 50.0, 25.0, 25.0], 16, granularity=2)
    assert sum(chips) == 16
    assert all(c % 2 == 0 for c in chips)
    assert chips[0] >= chips[1] >= chips[2]
    rates = service_rates([100.0, 50.0, 25.0, 25.0], chips, 1.0)
    # continuous flow: bottleneck service rate as high as an even split's
    even = service_rates([100.0, 50.0, 25.0, 25.0], [4] * 4, 1.0)
    assert min(rates) >= min(even) - 1e-9


# ---------------------------------------------------------------------------
# HLO collective parsing
# ---------------------------------------------------------------------------

_HLO = """
HloModule jit_step, entry_computation_layout={...}
  %x = bf16[1024,512]{1,0} parameter(0)
  %ag = bf16[1024,8192]{1,0} all-gather(bf16[1024,512]{1,0} %x), dimensions={1}
  %ar = f32[256,256]{1,0} all-reduce(f32[256,256]{1,0} %y), to_apply=%add
  %rs = bf16[128,512]{1,0} reduce-scatter(bf16[1024,512]{1,0} %z), dimensions={0}
  %a2a = bf16[64,64]{1,0} all-to-all(bf16[64,64]{1,0} %w), dimensions={0}
  %cp-start = (bf16[32,32], bf16[32,32]) collective-permute-start(bf16[32,32]{1,0} %v)
  %cp-done = bf16[32,32]{1,0} collective-permute-done(%cp-start)
  %mm = bf16[1024,1024]{1,0} dot(%a, %b)
"""


def test_collective_bytes_parsing():
    st_ = collective_bytes(_HLO)
    assert st_.bytes_by_kind["all-gather"] == 1024 * 8192 * 2
    assert st_.bytes_by_kind["all-reduce"] == 256 * 256 * 4
    assert st_.bytes_by_kind["reduce-scatter"] == 128 * 512 * 2
    assert st_.bytes_by_kind["all-to-all"] == 64 * 64 * 2
    # start/done pair counted once, tuple shape summed once
    assert st_.count_by_kind["collective-permute"] == 1
    assert st_.total_count == 5


def test_roofline_terms_math():
    # cost_analysis numbers are PER-DEVICE; model_flops is whole-step.
    t = roofline_terms({"flops": 197e12, "bytes accessed": 819e9},
                       _HLO, chips=256, model_flops=197e12 * 128)
    assert t.compute_s == pytest.approx(1.0)
    assert t.memory_s == pytest.approx(1.0)
    assert t.dominant in ("compute", "memory")
    assert t.roofline_fraction == pytest.approx(0.5, rel=0.01)
    assert t.useful_flops_ratio == pytest.approx(0.5, rel=0.01)


# ---------------------------------------------------------------------------
# VMEM accounting and conv routes (what the v5e compiler holds kernels to)
# ---------------------------------------------------------------------------


def test_padded_bytes_counts_lane_and_sublane_tiles():
    from repro.core.hw_specs import TPU_V5E
    from repro.core.tpu_tiles import padded_bytes

    # the stem's padded frame: 3 channels occupy 128 lanes, 230 rows 232
    assert padded_bytes((230, 230, 3), 4, TPU_V5E) == 230 * 232 * 128 * 4
    # packed bf16 rows come in 16s
    assert padded_bytes((230, 230, 3), 2, TPU_V5E) == 230 * 240 * 128 * 2
    assert padded_bytes((8, 128), 4, TPU_V5E) == 8 * 128 * 4
    assert padded_bytes((5,), 4, TPU_V5E) == 8 * 128 * 4


@pytest.mark.parametrize("hw,k,s", [(8, 3, 1), (8, 3, 2), (9, 7, 2), (7, 1, 2)])
def test_conv_geometry_taps_read_the_strided_windows(hw, k, s):
    """Tap (dy, dx) of the phase-split frame == the strided window
    x_pad[dy::s, dx::s] of the SAME-padded frame (§II-E)."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core.tpu_tiles import conv_geometry
    from repro.kernels.common import conv_taps, phase_split

    x = jnp.arange(2 * hw * hw * 3, dtype=jnp.float32).reshape(2, hw, hw, 3)
    geo = conv_geometry((hw, hw), (k, k), s)
    ho, wo = geo.out_hw
    assert ho == -(-hw // s)
    xp = np.pad(np.asarray(x), ((0, 0), *geo.pads, (0, 0)))
    xs = np.asarray(phase_split(x, geo))
    assert xs.shape[1] == len(geo.phases)
    for (dy, dx), (p, oy, ox) in conv_taps(geo, (k, k)):
        want = xp[:, dy : dy + (ho - 1) * s + 1 : s, dx : dx + (wo - 1) * s + 1 : s]
        np.testing.assert_array_equal(xs[:, p, oy : oy + ho, ox : ox + wo], want)


@pytest.mark.parametrize(
    "family", ["resnet18", "resnet34", "mobilenet_v1", "mobilenet_v2"]
)
def test_kernel_plan_at_224_fits_vmem_and_routes_only_stems(family):
    from repro.core.hw_specs import TPU_V5E
    from repro.core.tpu_tiles import vmem_budget
    from repro.models.registry import get_cnn_api

    api = get_cnn_api(family)
    gp = api.partition(api.make_config(), F(3), 1)
    kp = gp.kernel_plan(batch=8)
    budget = vmem_budget(TPU_V5E)
    tiled = {n: p for n, p in kp.items() if p.tile is not None}
    assert tiled
    for name, node in tiled.items():
        assert node.tile.vmem_bytes <= budget, (name, node.tile)
        # only the lane-sparse 3-channel stem overflows its frame budget
        assert node.tile.im2col == (name == "conv1"), (name, node.tile)


def test_tpu_spec_is_keyed_by_device_kind():
    from repro.core.hw_specs import TPU_V5E, target_spec, tpu_spec

    assert tpu_spec("TPU v5 lite") is TPU_V5E
    assert TPU_V5E.vmem_bytes == 128 * 1024**2
    with pytest.raises(KeyError, match="no TPUSpec"):
        tpu_spec("TPU v9 imaginary")
    # off a TPU, planning keeps v5e as its explicit target
    assert target_spec() is TPU_V5E
