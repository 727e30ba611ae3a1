"""Squeeze-and-excitation gate: y[n, h, w, c] = x[n, h, w, c] * g[n, c].

The 'scale' join of the rate graph (core.graph): the trunk of an
EfficientNet block times its frame's gate, one multiply per feature.  On
the FPGA it is one multiplier per stream lane, fed by a register holding
the frame's gate; on the TPU it is a pass over the trunk bound by memory,
so the kernel streams blocks of whole rows (``core.tpu_tiles.scale_tile``)
and keeps the frame's gate block resident while the rows walk past it.
The sigmoid stays on the gate's producer (its dense node), so the kernel
has no transcendental and runs exact float32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import kernel_name, pallas_call


def _scale_kernel(x_ref, g_ref, o_ref):
    """Grid: (n, c_blocks, row_blocks).  x, o: [1, bh, W, bc];
    g: [1, 1, bc]."""
    g = g_ref[0].astype(jnp.float32)  # [1, bc], broadcast over rows
    o_ref[...] = (x_ref[...].astype(jnp.float32) * g).astype(o_ref.dtype)


def se_scale_p(
    x: jax.Array,  # [N, H, W, C]
    g: jax.Array,  # [N, 1, C]
    *,
    bh: int,
    bc: int,
    node=None,
) -> jax.Array:
    n, h, w, c = x.shape
    assert g.shape == (n, 1, c), (x.shape, g.shape)
    assert h % bh == 0 and c % bc == 0, (x.shape, bh, bc)
    block = pl.BlockSpec((1, bh, w, bc), lambda nn, cc, hh: (nn, hh, 0, cc))
    return pallas_call(
        _scale_kernel,
        name=kernel_name("se_scale", node),
        grid=(n, c // bc, h // bh),
        in_specs=[
            block,
            pl.BlockSpec((1, 1, bc), lambda nn, cc, hh: (nn, 0, cc)),
        ],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
    )(x, g)
