"""KPU conv kernel vs XLA conv oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.kpu_conv import kpu_conv, kpu_conv_ref


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
