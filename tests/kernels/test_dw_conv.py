"""Depthwise KPU kernel vs XLA grouped-conv oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.dw_conv import dw_conv, dw_conv_ref


def _rand(key, shape, dtype=jnp.float32):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


@given(
    hw=st.sampled_from([5, 8, 14]),
    c=st.sampled_from([8, 16, 32, 96]),
    k=st.sampled_from([3, 5]),
    stride=st.sampled_from([1, 2]),
    dtype=st.sampled_from([jnp.float32, jnp.bfloat16]),
)
@settings(max_examples=20, deadline=None)
def test_dw_matches_ref(hw, c, k, stride, dtype):
    k1, k2 = jax.random.split(jax.random.key(0))
    x = _rand(k1, (2, hw, hw, c), dtype)
    w = _rand(k2, (k, k, c), dtype)
    got = dw_conv(x, w, stride=stride)
    want = dw_conv_ref(x, w, stride=stride)
    tol = 1e-4 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("bc", [8, 16, 32])
def test_dw_channel_tiles_equivalent(bc):
    """Different j tiles (channel BlockSpecs) — identical numerics."""
    k1, k2 = jax.random.split(jax.random.key(1))
    x = _rand(k1, (1, 8, 8, 32))
    w = _rand(k2, (3, 3, 32))
    got = dw_conv(x, w, bc=bc)
    np.testing.assert_allclose(got, dw_conv_ref(x, w), rtol=1e-4, atol=1e-4)


def test_dw_mobilenet_block():
    """MobileNet b2_dw: 96ch stride-2 — a pruned-phase (s=2) hot spot."""
    k1, k2 = jax.random.split(jax.random.key(2))
    x = _rand(k1, (1, 14, 14, 96))
    w = _rand(k2, (3, 3, 96))
    got = dw_conv(x, w, stride=2)
    assert got.shape == (1, 7, 7, 96)
    np.testing.assert_allclose(got, dw_conv_ref(x, w, stride=2),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("stride", [1, 2])
@pytest.mark.parametrize("hw, c", [(14, 40), (7, 96), (8, 48)])
def test_dw_5x5(hw, c, stride):
    """EfficientNet's 5x5 depthwise: SAME padding of 2 on each side at
    stride 1, and 1 before / 2 after (plus the phase round-up) at stride 2."""
    k1, k2 = jax.random.split(jax.random.key(3))
    x = _rand(k1, (2, hw, hw, c))
    w = _rand(k2, (5, 5, c))
    got = dw_conv(x, w, stride=stride)
    assert got.shape == (2, -(-hw // stride), -(-hw // stride), c)
    np.testing.assert_allclose(got, dw_conv_ref(x, w, stride=stride),
                               rtol=1e-4, atol=1e-4)
