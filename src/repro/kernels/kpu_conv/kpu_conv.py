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
  * multi-pixel P: every output position of the block's ``nb`` frames
    is computed per pass, i.e. P = nb * Ho * Wo rows through each
    weight tap: ``nb`` > 1 where the data rate has fallen (the small
    late-layer frames), so each weight block is fetched once per ``nb``
    frames;
  * sublane-aligned rows: each row streams ``wo_mxu`` columns, Wo
    rounded up to the sublane tile (``core.tpu_tiles.mxu_width``), so
    the window's rows merge into MXU rows without a relayout; the
    phases carry that many more zero columns on the right, and the
    outputs past Wo are dropped at the flush;
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
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import kernel_name, pallas_call


def _kpu_kernel(x_ref, w_ref, o_ref, acc_ref, *, taps: tuple, grid_ci: int):
    """Grid: (n/nb, co_blocks, ci_blocks).  Blocks:
    x: [nb, P, Hq, Wq, bci] (nb padded frames split into P stride
    phases), w: [kh, kw, bci, bco], o: [nb, Ho, Wo, bco], acc:
    [nb*Ho, Wo_mxu, bco].  ``taps`` gives per (dy, dx) the (phase, row,
    col) its window starts at."""
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    nb, ho, wo, bco = o_ref.shape
    wo_mxu = acc_ref.shape[1]
    bci = x_ref.shape[-1]
    # weight-stationary tap loop (static unroll = the C configurations);
    # the nb frames' windows merge into the leading (row) axis for free,
    # so one pass streams all nb * Ho * Wo_mxu positions.  Mosaic
    # collapses (rows, Wo_mxu) for the MXU, which is tile-aligned because
    # Wo_mxu is a sublane-tile multiple: at the true Wo (7, 14, 28) it
    # would relayout every window.
    for (dy, dx), (p, oy, ox) in taps:
        win = x_ref[:, p, oy : oy + ho, ox : ox + wo_mxu, :]
        acc_ref[...] += jax.lax.dot_general(
            win.reshape(nb * ho, wo_mxu, bci),
            w_ref[dy, dx],  # [bci, bco]
            dimension_numbers=(((2,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(ci == grid_ci - 1)
    def _flush():
        # only the true Wo columns leave the kernel
        acc = acc_ref[:, :wo, :] if wo_mxu != wo else acc_ref[...]
        o_ref[...] = acc.reshape(nb, ho, wo, bco).astype(o_ref.dtype)


def kpu_conv_p(
    x_phases: jax.Array,     # [N, P, Hq, Wq, d_in]  (padded, phase-split)
    w: jax.Array,            # [kh, kw, d_in, d_out]
    *,
    taps: tuple,
    out_hw: tuple,
    bci: int,
    bco: int,
    frames: int = 1,
    wo_mxu: Optional[int] = None,
    out_dtype=None,
    node=None,
) -> jax.Array:
    """``frames`` (nb) is the frames each grid step holds: its weight
    blocks are fetched once per nb frames.  ``wo_mxu`` (Wo without it) is
    the columns each tap window streams, of which the first Wo are
    output: the phases must be wide enough for every tap's window
    (``core.tpu_tiles.conv_geometry``)."""
    n, n_ph, hq, wq, d_in = x_phases.shape
    kh, kw, d_in2, d_out = w.shape
    assert d_in == d_in2
    assert d_in % bci == 0 and d_out % bco == 0, (
        f"(bci={bci}, bco={bco}) must divide ({d_in}, {d_out})"
    )
    assert n % frames == 0, f"frames={frames} must divide the batch {n}"
    ho, wo = out_hw
    wo_mxu = wo_mxu or wo
    assert all(ox + wo_mxu <= wq for _, (_, _, ox) in taps), (
        f"a {wo_mxu}-column tap window overruns the {wq}-column phases"
    )
    nb = frames
    grid = (n // nb, d_out // bco, d_in // bci)
    out_dtype = out_dtype or x_phases.dtype
    return pallas_call(
        functools.partial(_kpu_kernel, taps=taps, grid_ci=grid[2]),
        name=kernel_name("kpu_conv", node),
        grid=grid,
        in_specs=[
            pl.BlockSpec(
                (nb, n_ph, hq, wq, bci), lambda nn, co, ci: (nn, 0, 0, 0, ci)
            ),
            pl.BlockSpec((kh, kw, bci, bco), lambda nn, co, ci: (0, 0, ci, co)),
        ],
        out_specs=pl.BlockSpec(
            (nb, ho, wo, bco), lambda nn, co, ci: (nn, 0, 0, co)
        ),
        out_shape=jax.ShapeDtypeStruct((n, ho, wo, d_out), out_dtype),
        scratch_shapes=[pltpu.VMEM((nb * ho, wo_mxu, bco), jnp.float32)],
    )(x_phases, w)
