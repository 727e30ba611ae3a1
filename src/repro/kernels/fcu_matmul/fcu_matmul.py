"""FCU — the paper's fully-connected unit, as a Pallas TPU kernel.

FPGA FCU (Fig. 2): consumes j input features per clock, time-multiplexes h
neurons over C = h*d_in/j weight configurations, accumulating partials.

TPU translation (DESIGN.md §2):
  * j  -> bk, the contraction BlockSpec tile (must divide d_in — Eq. 7);
  * h  -> d_out / bn, the number of output tiles each resident input block
          serves (must divide d_out — Eq. 8);
  * C  -> grid_k, the accumulation trip count: the innermost grid
          dimension walks the weight "configurations" while the f32
          VMEM scratch accumulator plays the FCU's partial-sum register;
  * multi-pixel P -> bm output rows per pass (lane dimension).

The tile is chosen by ``core.tpu_tiles.select_tile`` — the same
HJ/BestRate exploration the paper runs for the FPGA.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import pallas_call


def _fcu_kernel(x_ref, w_ref, o_ref, acc_ref, *, grid_k: int):
    """One (bm x bk) @ (bk x bn) MXU pass; accumulate over the k grid."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        x_ref[...], w_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(k == grid_k - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def fcu_matmul_p(
    x: jax.Array,
    w: jax.Array,
    *,
    bm: int,
    bk: int,
    bn: int,
    out_dtype=None,
) -> jax.Array:
    """[m, d_in] @ [d_in, d_out] with explicit (bm, bk, bn) VMEM tiling.

    Requires bm | m, bk | d_in, bn | d_out (the paper's divisibility
    constraints — ops.py guarantees them via the DSE).
    """
    m, d_in = x.shape
    d_in2, d_out = w.shape
    assert d_in == d_in2, (x.shape, w.shape)
    assert m % bm == 0 and d_in % bk == 0 and d_out % bn == 0, (
        f"tiling ({bm},{bk},{bn}) must divide ({m},{d_in},{d_out})"
    )
    grid = (m // bm, d_out // bn, d_in // bk)
    out_dtype = out_dtype or x.dtype
    return pallas_call(
        functools.partial(_fcu_kernel, grid_k=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, d_out), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
    )(x, w)
