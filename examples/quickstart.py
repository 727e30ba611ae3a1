"""Quickstart: the paper's pipeline end-to-end in two minutes.

1. Build MobileNetV2, propagate the input data rate through all 54
   layers (watch it drop at every stride — the paper's core observation).
2. Run the (j,h) design-space exploration at the paper's 3/1 operating
   point and print the per-layer implementations + FPGA resource bill
   (Table II row).
3. Run actual inference in JAX, once with XLA convs and once with the
   Pallas KPU/FCU kernels (interpret mode), and check they agree.

Usage:  PYTHONPATH=src python examples/quickstart.py
"""
from fractions import Fraction as F

import jax
import jax.numpy as jnp

from repro.core import (estimate_network, fps, plan_network,
                        propagate_chain)
from repro.models import mobilenet as mn
from repro.models.registry import get_cnn_api

RATE = F(3, 1)   # 3 features/clock = 1 pixel/clock at the RGB input


def main() -> None:
    cfg = mn.MobileNetConfig(version=2, input_hw=(224, 224))
    chain = cfg.chain()

    print("=== 1. data-rate propagation (features/clock) ===")
    pts = propagate_chain(RATE, chain)
    for spec, pt in list(zip(chain, pts[1:]))[:12]:
        q = pt.pixels_per_clock
        print(f"  {spec.name:>12}  ->  r={str(pt.features_per_clock):>9} "
              f"(pixels/clk {str(q):>8})")
    print("  ... rate falls 16x by the last stride stage\n")

    print("=== 2. (j,h) DSE + resource bill @ r=3/1 ===")
    impls = plan_network(chain, RATE)
    for impl in impls[:8]:
        print(f"  {impl.layer.name:>12}: j={impl.j:<4} h={impl.h:<4} "
              f"C={impl.configs:<6} units={impl.units:<5} "
              f"util={float(impl.utilization):.2f}")
    est = estimate_network(impls).rounded()
    print(f"  TOTAL: {est}  |  paper Table II row: DSP 3168, LUT 124k")
    print(f"  FPS @ 404.53 MHz: {fps((224, 224), RATE / 3, 404.53e6):.1f} "
          f"(paper: 8026.4)\n")

    print("=== 3. JAX inference: XLA vs Pallas KPU/FCU kernels ===")
    api = get_cnn_api("mobilenet_v2")
    small = api.make_config(input_hw=(32, 32), num_classes=10)
    params = api.init(small, jax.random.key(0))
    x = jax.random.normal(jax.random.key(1), (1, 32, 32, 3))
    base = api.apply(params, x, small)

    from repro.kernels.dw_conv import dw_conv
    from repro.kernels.fcu_matmul import fcu_matmul
    from repro.kernels.kpu_conv import kpu_conv
    kern = api.apply(params, x, small, conv_impls={
        "conv": lambda a, w, s: kpu_conv(a, w, stride=s),
        "dwconv": lambda a, w, s: dw_conv(a, w[:, :, 0, :], stride=s),
        "pointwise": lambda a, w: fcu_matmul(a, w),
    })
    err = float(jnp.max(jnp.abs(base - kern)))
    print(f"  max |XLA - kernels| = {err:.2e}  (tolerance 2e-3)")
    assert err < 2e-3
    print("  OK — kernels are numerically neutral; the DSE only changes "
          "the schedule.")


if __name__ == "__main__":
    main()
