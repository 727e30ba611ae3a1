"""KPU — the paper's kernel processing unit, re-thought for the TPU.

FPGA KPU (Figs. 1, 4-6): K*K multipliers per input channel, sliding-window
delay lines, phase-specialized copies for multi-pixel processing, pruned
phases under stride.

TPU translation (DESIGN.md §2): the delay-line has no TPU analogue — a
VMEM-resident input block *is* the shared, non-transposed input buffer of
the improved KPU (paper Fig. 5: "the input features ... can be buffered
once, and then shared with all other KPUs in the layer").  What transfers
is the schedule:

  * weight-stationary tap accumulation: for each of the K*K taps we run
    one MXU pass  x_shifted[(Ho*Wo), bci] @ w_tap[bci, bco]  and
    accumulate in an f32 VMEM scratch — the KPU's adder tree becomes the
    MXU's systolic reduction + scratch accumulation;
  * multi-pixel P: every output position of the block is computed per
    pass (the lane dimension), i.e. P = Wo;
  * stride pruning (§II-E): the padded frame arrives split into its
    stride phases (``kernels.common.phase_split``), so every tap reads
    one contiguous window of one phase — only surviving-phase windows
    exist, the moral equivalent of deleting pruned KPUs, and the kernel
    never slices with a stride;
  * j -> bci input-channel tile (j | d_in), h -> d_out/bco output tile
    trips (h | d_out), C -> the (ci, tap) accumulation trip count.

Input must be pre-padded and phase-split (ops.py does 'SAME' padding);
the pad-select signals of the FPGA become plain zero padding here.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import pallas_call


def _kpu_kernel(x_ref, w_ref, o_ref, acc_ref, *, taps: tuple, grid_ci: int):
    """Grid: (n, co_blocks, ci_blocks).  Blocks:
    x: [1, P, Hq, Wq, bci] (padded frame split into P stride phases),
    w: [kh, kw, bci, bco], o/acc: [1, Ho, Wo, bco].  ``taps`` gives per
    (dy, dx) the (phase, row, col) its window starts at."""
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    _, ho, wo, _ = acc_ref.shape
    # weight-stationary tap loop (static unroll = the C configurations)
    for (dy, dx), (p, oy, ox) in taps:
        win = x_ref[0, p, oy : oy + ho, ox : ox + wo, :]  # [Ho, Wo, bci]
        acc_ref[0] += jax.lax.dot_general(
            win,
            w_ref[dy, dx],  # [bci, bco]
            dimension_numbers=(((2,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(ci == grid_ci - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def kpu_conv_p(
    x_phases: jax.Array,     # [N, P, Hq, Wq, d_in]  (padded, phase-split)
    w: jax.Array,            # [kh, kw, d_in, d_out]
    *,
    taps: tuple,
    out_hw: tuple,
    bci: int,
    bco: int,
    out_dtype=None,
) -> jax.Array:
    n, n_ph, hq, wq, d_in = x_phases.shape
    kh, kw, d_in2, d_out = w.shape
    assert d_in == d_in2
    assert d_in % bci == 0 and d_out % bco == 0, (
        f"(bci={bci}, bco={bco}) must divide ({d_in}, {d_out})"
    )
    ho, wo = out_hw
    grid = (n, d_out // bco, d_in // bci)
    out_dtype = out_dtype or x_phases.dtype
    return pallas_call(
        functools.partial(_kpu_kernel, taps=taps, grid_ci=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec(
                (1, n_ph, hq, wq, bci), lambda nn, co, ci: (nn, 0, 0, 0, ci)
            ),
            pl.BlockSpec((kh, kw, bci, bco), lambda nn, co, ci: (0, 0, ci, co)),
        ],
        out_specs=pl.BlockSpec((1, ho, wo, bco), lambda nn, co, ci: (nn, 0, 0, co)),
        out_shape=jax.ShapeDtypeStruct((n, ho, wo, d_out), out_dtype),
        scratch_shapes=[pltpu.VMEM((1, ho, wo, bco), jnp.float32)],
    )(x_phases, w)
