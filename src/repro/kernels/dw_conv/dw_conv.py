"""Depthwise KPU — MobileNet's hot spot, VPU flavour.

Depthwise convolution has no cross-channel reduction, so the MXU is
useless: the FPGA paper keeps these multipliers in soft logic (our
calibration confirmed its DSP counts only fit that way), and the TPU
analogue is the VPU (8x128 vector unit) doing elementwise
multiply-accumulate over the K*K taps.

Per §II-B: "the channel multiplier replaces d_out"; here cm=1 (MobileNet)
and h=1, so the layer is just j-channel-parallel tap accumulation; the
channel BlockSpec tile is the paper's j (j | d_in).  Stride pruning is the
same phase split as kpu_conv: each tap reads one contiguous window of one
stride phase.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import pallas_call


def _dw_kernel(x_ref, w_ref, o_ref, *, taps: tuple):
    """Grid: (n, c_blocks). x: [1, P, Hq, Wq, bc] (padded frame split
    into P stride phases), w: [kh, kw, bc], o: [1, Ho, Wo, bc]."""
    _, ho, wo, bc = o_ref.shape
    acc = jnp.zeros((ho, wo, bc), jnp.float32)
    for (dy, dx), (p, oy, ox) in taps:
        win = x_ref[0, p, oy : oy + ho, ox : ox + wo, :]
        acc += win.astype(jnp.float32) * w_ref[dy, dx].astype(jnp.float32)
    o_ref[0] = acc.astype(o_ref.dtype)


def dw_conv_p(
    x_phases: jax.Array,   # [N, P, Hq, Wq, C]  (padded, phase-split)
    w: jax.Array,          # [kh, kw, C]
    *,
    taps: tuple,
    out_hw: tuple,
    bc: int,
    out_dtype=None,
) -> jax.Array:
    n, n_ph, hq, wq, c = x_phases.shape
    kh, kw, c2 = w.shape
    assert c == c2 and c % bc == 0, (x_phases.shape, w.shape, bc)
    ho, wo = out_hw
    out_dtype = out_dtype or x_phases.dtype
    return pallas_call(
        functools.partial(_dw_kernel, taps=taps),
        grid=(n, c // bc),
        in_specs=[
            pl.BlockSpec((1, n_ph, hq, wq, bc), lambda nn, cc: (nn, 0, 0, 0, cc)),
            pl.BlockSpec((kh, kw, bc), lambda nn, cc: (0, 0, cc)),
        ],
        out_specs=pl.BlockSpec((1, ho, wo, bc), lambda nn, cc: (nn, 0, 0, cc)),
        out_shape=jax.ShapeDtypeStruct((n, ho, wo, c), out_dtype),
    )(x_phases, w)
