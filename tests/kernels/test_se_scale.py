"""Squeeze-and-excitation gate kernel vs the jnp oracle (interpret mode
on the CPU): one float32 multiply per feature, so the two agree exactly."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.se_scale import se_scale, se_scale_impl, se_scale_ref


def _case(key, shape, dtype=jnp.float32):
    k1, k2 = jax.random.split(jax.random.key(key))
    n, _, _, c = shape
    x = jax.random.normal(k1, shape, jnp.float32).astype(dtype)
    g = jax.nn.sigmoid(jax.random.normal(k2, (n, c), jnp.float32)).astype(dtype)
    return x, g


@pytest.mark.parametrize("shape, bh, bc", [
    ((2, 8, 8, 32), None, None),     # the block rule's own choice
    ((2, 8, 8, 32), 2, 32),          # four row blocks
    ((3, 7, 7, 256), 7, 128),        # two channel blocks, odd frame
    ((2, 14, 14, 144), 7, 144),      # EfficientNet b3-like width
    ((1, 4, 4, 8), 1, 8),            # a block per row
])
def test_se_scale_matches_ref(shape, bh, bc):
    x, g = _case(0, shape)
    got = se_scale(x, g, bh=bh, bc=bc)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(se_scale_ref(x, g)))


def test_se_scale_bfloat16():
    x, g = _case(1, (2, 8, 8, 128), jnp.bfloat16)
    got = se_scale(x, g)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(se_scale_ref(x, g), np.float32))


def test_se_scale_gates_each_frame_with_its_own_gate():
    x = jnp.ones((3, 4, 4, 8), jnp.float32)
    g = jnp.arange(24, dtype=jnp.float32).reshape(3, 8)
    got = np.asarray(se_scale(x, g))
    for n in range(3):
        assert np.array_equal(got[n], np.broadcast_to(np.asarray(g[n]), (4, 4, 8)))


def test_se_scale_impl_records_its_tile():
    from repro.core.tpu_tiles import TileChoice

    tile = TileChoice(bm=2 * 8, bk=32, bn=1, grid_m=4, grid_k=1, grid_n=1,
                      vmem_bytes=0, mxu_aligned=False)
    seen = {}
    impl = se_scale_impl(tile=tile, record=lambda **t: seen.update(t),
                         node="b1_scale")
    x, g = _case(2, (2, 8, 8, 32))
    np.testing.assert_array_equal(np.asarray(impl(x, g)),
                                  np.asarray(se_scale_ref(x, g)))
    assert seen == {"bk": 32, "bn": 1, "bm": 16, "d_in": 32, "d_out": 32}
