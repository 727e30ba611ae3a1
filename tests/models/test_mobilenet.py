"""MobileNet E2E: JAX model + Pallas-kernel-backed layers + int8 path."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import mobilenet as mn
from repro.models.registry import get_cnn_api

V2 = get_cnn_api("mobilenet_v2")


@pytest.fixture(scope="module")
def small_cfg():
    # reduced input keeps interpret-mode kernels fast; full channel plan
    return mn.MobileNetConfig(version=2, input_hw=(32, 32), num_classes=10)


@pytest.fixture(scope="module")
def v1_cfg():
    return mn.MobileNetConfig(version=1, input_hw=(32, 32), num_classes=10)


def test_chain_matches_params(small_cfg):
    params = V2.init(small_cfg, jax.random.key(0))
    chain = small_cfg.chain()
    named = {s.name for s in chain if s.kind not in ("gap", "pool", "add")}
    assert named == set(params)


@pytest.mark.parametrize("version", [1, 2])
def test_forward_shapes_finite(version):
    cfg = mn.MobileNetConfig(version=version, input_hw=(32, 32),
                             num_classes=10)
    api = get_cnn_api(f"mobilenet_v{version}")
    params = api.init(cfg, jax.random.key(0))
    x = jax.random.normal(jax.random.key(1), (2, 32, 32, 3))
    logits = api.apply(params, x, cfg)
    assert logits.shape == (2, 10)
    assert bool(jnp.all(jnp.isfinite(logits)))


def test_kernel_backed_equals_xla(small_cfg):
    """Swapping XLA convs for the Pallas KPU/FCU/DW kernels is numerically
    neutral — the DSE changes schedules, never math."""
    from repro.kernels.dw_conv import dw_conv
    from repro.kernels.fcu_matmul import fcu_matmul
    from repro.kernels.kpu_conv import kpu_conv

    params = V2.init(small_cfg, jax.random.key(0))
    x = jax.random.normal(jax.random.key(1), (1, 32, 32, 3))
    base = V2.apply(params, x, small_cfg)
    impls = {
        "conv": lambda a, w, s: kpu_conv(a, w, stride=s),
        "dwconv": lambda a, w, s: dw_conv(a, w[:, :, 0, :], stride=s),
        "pointwise": lambda a, w: fcu_matmul(a, w),
    }
    kern = V2.apply(params, x, small_cfg, conv_impls=impls)
    np.testing.assert_allclose(np.asarray(kern), np.asarray(base),
                               rtol=2e-3, atol=2e-3)


def test_int8_quantization_close(small_cfg):
    """The paper's 8-bit datapath: int8 weights track float within the
    quantization budget and preserve top-1 agreement on most inputs."""
    params = V2.init(small_cfg, jax.random.key(0))
    x = jax.random.normal(jax.random.key(1), (8, 32, 32, 3))
    ref = V2.apply(params, x, small_cfg)
    qp, scales = V2.quantize(params)
    got = V2.apply_int8(qp, scales, x, small_cfg)
    assert got.shape == ref.shape
    agree = float(jnp.mean((jnp.argmax(got, -1) == jnp.argmax(ref, -1))))
    assert agree >= 0.75, f"top-1 agreement {agree}"


def test_residual_blocks_active(small_cfg):
    """V2's linear bottleneck residuals must actually fire (shape-matched
    blocks exist in the chain)."""
    chain = small_cfg.chain()
    projects = [s for s in chain if s.name.endswith("_project")]
    assert len(projects) == 17
