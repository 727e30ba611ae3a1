"""KPU conv kernel vs XLA conv oracle."""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.tpu_tiles import TileChoice, conv_geometry, group_blocks
from repro.kernels.common import conv_taps, phase_split
from repro.kernels.kpu_conv import conv_impl, kpu_conv, kpu_conv_ref
from repro.kernels.kpu_conv.kpu_conv import kpu_conv_p
from repro.kernels.kpu_conv.ops import block_frames

# the kernel module (the package exports the function of the same name)
KPU = importlib.import_module("repro.kernels.kpu_conv.kpu_conv")


def _rand(key, shape, dtype=jnp.float32):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


@given(
    hw=st.sampled_from([5, 8, 12, 16]),
    cin=st.sampled_from([3, 8, 16]),
    cout=st.sampled_from([8, 16, 32]),
    k=st.sampled_from([1, 3, 5]),
    stride=st.sampled_from([1, 2]),
    dtype=st.sampled_from([jnp.float32, jnp.bfloat16]),
)
@settings(max_examples=25, deadline=None)
def test_kpu_conv_matches_ref(hw, cin, cout, k, stride, dtype):
    k1, k2 = jax.random.split(jax.random.key(0))
    x = _rand(k1, (2, hw, hw, cin), dtype)
    w = _rand(k2, (k, k, cin, cout), dtype)
    got = kpu_conv(x, w, stride=stride)
    want = kpu_conv_ref(x, w, stride=stride)
    tol = 1e-4 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_kpu_paper_example_5x5_3x3_2px():
    """Paper Fig. 5: 5x5 feature map, 3x3 kernel, multi-pixel processing."""
    k1, k2 = jax.random.split(jax.random.key(1))
    x = _rand(k1, (1, 5, 5, 3))
    w = _rand(k2, (3, 3, 3, 8))
    got = kpu_conv(x, w, stride=1)
    np.testing.assert_allclose(got, kpu_conv_ref(x, w), rtol=1e-4, atol=1e-4)


def test_kpu_stride2_prunes_phases():
    """Stride 2 output == every-2nd-window of stride-1 output (§II-E:
    pruned phases produce exactly the skipped windows)."""
    k1, k2 = jax.random.split(jax.random.key(2))
    x = _rand(k1, (1, 8, 8, 4))
    w = _rand(k2, (3, 3, 4, 8))
    s1 = kpu_conv(x, w, stride=1)
    s2 = kpu_conv(x, w, stride=2)
    # SAME padding for k=3: s=1 pads (1,1); s=2 on even size pads (0,1),
    # so the phase alignment offset is 1 row/col.
    np.testing.assert_allclose(s2, s1[:, 1::2, 1::2, :], rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("bci,bco", [(1, 8), (4, 4), (8, 16), (16, 2)])
def test_kpu_tilings_equivalent(bci, bco):
    k1, k2 = jax.random.split(jax.random.key(3))
    x = _rand(k1, (1, 6, 6, 16))
    w = _rand(k2, (3, 3, 16, 16))
    got = kpu_conv(x, w, bci=bci, bco=bco)
    np.testing.assert_allclose(got, kpu_conv_ref(x, w), rtol=1e-4, atol=1e-4)


def test_kpu_first_layer_mobilenet_shape():
    """conv1 of MobileNet: 3->32, stride 2 — the paper's entry layer."""
    k1, k2 = jax.random.split(jax.random.key(4))
    x = _rand(k1, (1, 16, 16, 3))
    w = _rand(k2, (3, 3, 3, 32))
    got = kpu_conv(x, w, stride=2)
    assert got.shape == (1, 8, 8, 32)
    np.testing.assert_allclose(got, kpu_conv_ref(x, w, stride=2),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize(
    "hw,k,stride,cin",
    [(12, 7, 2, 3), (9, 3, 2, 5), (8, 3, 1, 8), (8, 1, 2, 16)],
)
def test_kpu_im2col_route_matches_ref(hw, k, stride, cin):
    """The im2col route (patches through the FCU matmul — the stems whose
    whole frame overflows VMEM) computes the same conv as the KPU route."""
    k1, k2 = jax.random.split(jax.random.key(5))
    x = _rand(k1, (2, hw, hw, cin))
    w = _rand(k2, (k, k, cin, 16))
    got = kpu_conv(x, w, stride=stride, bci=cin, bco=16, bm=8, im2col=True)
    kpu = kpu_conv(x, w, stride=stride, im2col=False)
    want = kpu_conv_ref(x, w, stride=stride)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(got, kpu, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("nb", [2, 4, 8])
@pytest.mark.parametrize(
    "hw,stride",
    [(7, 1), (14, 1), (14, 2)],
    ids=["7x7", "14x14", "stride2-4phase"],
)
def test_kpu_frames_per_step_match_one_frame(hw, stride, nb):
    """nb frames per grid step (the plan's multi-pixel P over frames)
    compute each frame's sums exactly as one frame per step does."""
    k1, k2 = jax.random.split(jax.random.key(6))
    x = _rand(k1, (8, hw, hw, 128))
    w = _rand(k2, (3, 3, 128, 128))
    geo = conv_geometry((hw, hw), (3, 3), stride)
    ho, wo = geo.out_hw
    xs, taps = phase_split(x, geo), conv_taps(geo, (3, 3))
    assert xs.shape[1] == (4 if stride == 2 else 1)

    def run(frames):
        return kpu_conv_p(xs, w, taps=taps, out_hw=geo.out_hw, bci=128,
                          bco=128, frames=frames)

    got = run(nb)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(run(1)))
    np.testing.assert_allclose(got, kpu_conv_ref(x, w, stride=stride),
                               rtol=1e-4, atol=1e-4)
    # the wrapper holds as many frames as the pixel tile bm covers
    assert block_frames(8, geo, (3, 3), 128, 128, nb * ho * wo, 4) == nb
    wrapped = kpu_conv(x, w, stride=stride, bci=128, bco=128,
                       bm=nb * ho * wo, im2col=False)
    np.testing.assert_array_equal(np.asarray(wrapped), np.asarray(got))


# output width -> the row width the MXU streams at float32: the width
# rounded up to the 8-row sublane tile (8 itself is the aligned control)
WO_MXU = {7: 8, 8: 8, 14: 16, 28: 32}


@pytest.mark.parametrize(
    "k,stride", [(3, 1), (3, 2), (1, 2)], ids=["3x3-s1", "3x3-s2", "1x1-s2"]
)
@pytest.mark.parametrize("wo", sorted(WO_MXU))
def test_kpu_aligned_rows_keep_the_true_output_width(wo, k, stride):
    """An output width that is not a sublane multiple streams through the
    MXU at the aligned width; the result keeps the true width, matches
    the oracle, and is the same bits at every frames-per-step nb."""
    n, cin, cout = 8, 16, 16
    k1, k2 = jax.random.split(jax.random.key(7))
    x = _rand(k1, (n, wo * stride, wo * stride, cin))
    w = _rand(k2, (k, k, cin, cout))
    want = kpu_conv_ref(x, w, stride=stride)
    runs = {}
    for nb in (1, 2, 8):
        tile = TileChoice(bm=nb * wo * wo, bk=cin, bn=cout, grid_m=1,
                          grid_k=1, grid_n=1, vmem_bytes=0, mxu_aligned=False)
        rec = {}
        y = conv_impl(tile=tile, record=lambda **t: rec.update(t))(x, w, stride)
        assert y.shape == (n, wo, wo, cout)
        assert (rec["frames"], rec["wo_mxu"]) == (nb, WO_MXU[wo])
        np.testing.assert_allclose(y, want, rtol=1e-4, atol=1e-4)
        runs[nb] = np.asarray(y)
    for nb in (2, 8):
        np.testing.assert_array_equal(runs[nb], runs[1])


# (groups, group width, stride, groups a grid step): every gpb the rule
# can pick for RegNetY's 64-channel groups (``group_blocks``), and the
# whole block for 4-channel groups, where no fewer fill a lane tile
GROUPED = [
    (g, gi, s, gpb)
    for gi in (64, 4)
    for g in (2, 3, 8, 17)
    for s in (1, 2)
    for gpb in group_blocks(g, gi, gi, 128)
]


@pytest.mark.parametrize(
    "groups,gi,stride,gpb", GROUPED,
    ids=[f"G{g}-w{gi}-s{s}-gpb{b}" for g, gi, s, b in GROUPED],
)
def test_kpu_grouped_route_matches_lax(groups, gi, stride, gpb, monkeypatch):
    """The grouped route against XLA's grouped conv: a grouped HWIO
    weight, whole groups a grid step, both in-kernel passes (the rule's
    block-diagonal chunks, and one group at a time)."""
    k1, k2 = jax.random.split(jax.random.key(groups * gi + stride))
    c, hw = groups * gi, 6
    x = _rand(k1, (2, hw, hw, c))
    w = _rand(k2, (3, 3, gi, c))
    want = jax.lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), feature_group_count=groups,
        precision=jax.lax.Precision.HIGHEST)
    geo = conv_geometry((hw, hw), (3, 3), stride)
    xs, taps = phase_split(x, geo), conv_taps(geo, (3, 3))
    for one_group in (False, True):
        with monkeypatch.context() as m:
            if one_group:  # steer the kernel to one group per MXU pass
                m.setattr(KPU, "groups_per_dot", lambda gpb, gi: 1)
            got = kpu_conv_p(xs, w, taps=taps, out_hw=geo.out_hw,
                             bci=gpb * gi, bco=gpb * gi, frames=2)
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    if gpb == groups:  # the entry point without a plan picks its own block
        np.testing.assert_allclose(kpu_conv(x, w, stride=stride), want,
                                   rtol=1e-4, atol=1e-4)
    # the planned entry point records the grouped route it ran
    tile = TileChoice(bm=2 * 9, bk=gpb * gi, bn=gpb * gi, grid_m=1, grid_k=1,
                      grid_n=groups // gpb, vmem_bytes=0, mxu_aligned=False)
    rec = {}
    y = conv_impl(tile=tile, record=lambda **t: rec.update(t))(x, w, stride)
    np.testing.assert_allclose(y, want, rtol=1e-4, atol=1e-4)
    assert (rec["groups"], rec["gpb"]) == (groups, gpb)
