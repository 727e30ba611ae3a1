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
    trips (h | d_out), C -> the (ci, tap) accumulation trip count;
  * grouped conv (the weight ``[kh, kw, d_in/G, d_out]`` reads fewer
    channels than the frame holds): each grid step holds ``gpb`` whole
    groups, reads only their input channels and contracts them in one
    step; each MXU pass takes ``groups_per_dot`` groups against a
    block-diagonal weight tile built in VMEM, so no zero-filled dense
    weight ever leaves the kernel.

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

MXU_DEPTH = 128  # contraction rows of one MXU pass (v5e: 128x128)


def groups_per_dot(gpb: int, group_in: int) -> int:
    """Groups each MXU pass of the grouped route contracts together
    (the last pass of a step the rest): as many as fill the MXU's
    contraction depth (two groups of 64 channels), through a
    block-diagonal weight tile built in VMEM; one where a group fills
    it alone.  On a TPU v5e this beat one group a pass and one tile of
    all the step's groups at every RegNetY-4.0GF shape (the times are in
    docs/kernels.md)."""
    return min(gpb, max(1, MXU_DEPTH // group_in))


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


def _kpu_group_kernel(x_ref, w_ref, o_ref, acc_ref, *, taps: tuple):
    """The grouped route.  Grid: (n/nb, group blocks).  Blocks: x
    [nb, P, Hq, Wq, gpb*gi] (the step's own groups' input channels),
    w [kh, kw, gi, gpb*go] (grouped HWIO), o [nb, Ho, Wo, gpb*go], acc
    [nb*Ho, Wo_mxu, gpb*go].  Each MXU pass contracts a chunk of ``gpd``
    groups (the last chunk the rest): their channels of the tap window
    against a block-diagonal [gpd*gi, gpd*go] tile that only VMEM ever
    holds (the weight chunk stacked ``gpd`` times, zero off its
    groups).  A chunk starts at a multiple of gpd*gi channels: lane
    aligned where gpd*gi is a lane multiple."""
    nb, ho, wo, bco = o_ref.shape
    wo_mxu = acc_ref.shape[1]
    gi = w_ref.shape[2]
    gpb = x_ref.shape[-1] // gi
    go = bco // gpb
    gpd = groups_per_dot(gpb, gi)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    for g0 in range(0, gpb, gpd):
        k = min(gpd, gpb - g0)  # groups in this chunk
        xs, ws = slice(g0 * gi, (g0 + k) * gi), slice(g0 * go, (g0 + k) * go)
        if k > 1:
            diag = (
                jax.lax.broadcasted_iota(jnp.int32, (k * gi, k * go), 0) // gi
                == jax.lax.broadcasted_iota(jnp.int32, (k * gi, k * go), 1) // go
            )
        for (dy, dx), (p, oy, ox) in taps:
            win = x_ref[:, p, oy : oy + ho, ox : ox + wo_mxu, xs]
            w = w_ref[dy, dx, :, ws]  # [gi, k*go]
            if k > 1:
                w = jnp.where(diag, jnp.concatenate([w] * k, axis=0), 0)
            acc_ref[:, :, ws] += jax.lax.dot_general(
                win.reshape(nb * ho, wo_mxu, k * gi),
                w,
                dimension_numbers=(((2,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
    acc = acc_ref[:, :wo, :] if wo_mxu != wo else acc_ref[...]
    o_ref[...] = acc.reshape(nb, ho, wo, bco).astype(o_ref.dtype)


def kpu_conv_p(
    x_phases: jax.Array,     # [N, P, Hq, Wq, d_in]  (padded, phase-split)
    w: jax.Array,            # [kh, kw, d_in / groups, d_out]
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
    (``core.tpu_tiles.conv_geometry``).

    A weight with fewer input channels than ``x`` is a grouped conv's
    (G = d_in / w's input channels): it runs the grouped route, whose
    channel blocks hold whole groups (``bco`` a multiple of d_out/G,
    ``bci`` the same groups' inputs; ``kpu_group_conv_p``)."""
    n, n_ph, hq, wq, d_in = x_phases.shape
    kh, kw, d_in2, d_out = w.shape
    if d_in2 != d_in:
        y, gpb = kpu_group_conv_p(
            x_phases, w, taps=taps, out_hw=out_hw, bco=bco, frames=frames,
            wo_mxu=wo_mxu, out_dtype=out_dtype, node=node,
        )
        assert bci == gpb * d_in2, f"bci={bci} must hold the {gpb} groups' inputs"
        return y
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


def kpu_group_conv_p(
    x_phases: jax.Array,     # [N, P, Hq, Wq, d_in]  (padded, phase-split)
    w: jax.Array,            # [kh, kw, d_in / groups, d_out]
    *,
    taps: tuple,
    out_hw: tuple,
    bco: int,
    frames: int = 1,
    wo_mxu: Optional[int] = None,
    out_dtype=None,
    node=None,
) -> tuple:
    """The grouped route: ``bco`` output channels (whole groups) a grid
    step, which reads the same groups' input channels.  Returns the
    output and ``gpb``, the groups each grid step of the built call
    holds."""
    n, n_ph, hq, wq, d_in = x_phases.shape
    kh, kw, gi, d_out = w.shape
    groups = d_in // gi
    assert d_in == groups * gi and d_out % groups == 0, (
        f"{d_in} -> {d_out} channels cannot split into groups of {gi} inputs"
    )
    go = d_out // groups
    gpb = bco // go
    assert bco == gpb * go and groups % gpb == 0, (
        f"bco={bco} must hold whole groups of {go} outputs"
    )
    bci = gpb * gi
    assert n % frames == 0, f"frames={frames} must divide the batch {n}"
    ho, wo = out_hw
    wo_mxu = wo_mxu or wo
    assert all(ox + wo_mxu <= wq for _, (_, _, ox) in taps), (
        f"a {wo_mxu}-column tap window overruns the {wq}-column phases"
    )
    nb = frames
    y = pallas_call(
        functools.partial(_kpu_group_kernel, taps=taps),
        name=kernel_name("kpu_conv", node),
        grid=(n // nb, groups // gpb),
        in_specs=[
            pl.BlockSpec((nb, n_ph, hq, wq, bci), lambda nn, g: (nn, 0, 0, 0, g)),
            pl.BlockSpec((kh, kw, gi, bco), lambda nn, g: (0, 0, 0, g)),
        ],
        out_specs=pl.BlockSpec((nb, ho, wo, bco), lambda nn, g: (nn, 0, 0, g)),
        out_shape=jax.ShapeDtypeStruct((n, ho, wo, d_out), out_dtype or x_phases.dtype),
        scratch_shapes=[pltpu.VMEM((nb * ho, wo_mxu, bco), jnp.float32)],
    )(x_phases, w)
    return y, gpb
