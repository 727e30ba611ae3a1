"""jit'd wrapper: SAME padding, stride phases + DSE-derived channel tiling.

Two ways to pick the (bci, bco) channel tiles:

  * uniform — no ``tile``: ``select_tile`` runs the BestRate search with
    one (optional) global ``rate`` for every layer;
  * rate-matched — ``conv_impl(tile=...)`` receives one node's
    plan-derived ``TileChoice`` (``GraphPlan.kernel_plan``) and executes
    exactly that tiling; the optional ``record`` callback reports the
    executed tile back to the caller (models/cnn.py asserts it against
    the plan per node).

Two routes run a conv: the whole-frame KPU kernel on the phase-split
padded input, holding as many frames per grid step as the pixel tile
``bm`` covers (``block_frames``), its tap windows widened to the
sublane tile (``frame_geometry``), or — when that frame cannot fit VMEM
(the lane-sparse 3-channel stems; ``TileChoice.im2col``, or the same
budget check on the uniform path) — im2col patches through the FCU
matmul kernel.  A grouped conv (a weight ``[kh, kw, d_in/G, d_out]``)
always takes the whole-frame kernel's grouped route, ``bco`` holding
whole groups.
"""
from __future__ import annotations

import functools
from fractions import Fraction
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.core.hw_specs import target_spec
from repro.core.tpu_tiles import (
    ConvGeometry,
    TileChoice,
    conv_block_frames,
    conv_frame_vmem_bytes,
    conv_geometry,
    fit_bm,
    groups_per_block,
    mxu_width,
    select_tile,
    vmem_budget,
)
from repro.kernels.common import conv_taps, phase_split
from repro.kernels.fcu_matmul.fcu_matmul import fcu_matmul_p
from .kpu_conv import kpu_conv_p, kpu_group_conv_p


def frame_geometry(in_hw, kernel, stride: int, dtype_bytes: int) -> ConvGeometry:
    """The whole-frame route's geometry: ``conv_geometry`` with every tap
    window ``mxu_width`` columns wide (the output width itself where that
    is a sublane-tile multiple)."""
    wo = conv_geometry(in_hw, kernel, stride).out_hw[1]
    wo_mxu = mxu_width(wo, dtype_bytes, target_spec())
    return conv_geometry(in_hw, kernel, stride, wo_mxu)


def _im2col(x: jax.Array, geo: ConvGeometry, kernel) -> jax.Array:
    """[N, H, W, C] -> [N*Ho*Wo, kh*kw*C] patches in (dy, dx, c) order —
    the row order of the HWIO weight reshaped to [kh*kw*C, d_out]."""
    xs = phase_split(x, geo)
    ho, wo = geo.out_hw
    cols = [
        xs[:, p, oy : oy + ho, ox : ox + wo, :]
        for _, (p, oy, ox) in conv_taps(geo, kernel)
    ]
    # stacked on a tap axis, not concatenated on the lane axis: each
    # [N, Ho, Wo, C] window of a 3-channel stem would be lane-padded to
    # 128 in HBM (2.5 GB of temporaries for ResNet-18 at micro-batch 8,
    # 164 MB this way, in a compile for v5e)
    patches = jnp.stack(cols, axis=3)
    return patches.reshape(-1, len(cols) * x.shape[-1])


@functools.partial(
    jax.jit,
    static_argnames=("stride", "rate", "bci", "bco", "bm", "im2col", "node"),
)
def kpu_conv(
    x: jax.Array,            # [N, H, W, d_in]
    w: jax.Array,            # [kh, kw, d_in, d_out]
    *,
    stride: int = 1,
    rate: Optional[Fraction] = None,
    bci: Optional[int] = None,
    bco: Optional[int] = None,
    bm: Optional[int] = None,
    im2col: Optional[bool] = None,
    node: Optional[str] = None,
) -> jax.Array:
    """``im2col=None`` picks the route by the frame's VMEM working set.
    ``bm`` is the pixel tile: of the FCU matmul on the im2col route; on
    the whole-frame route it sets the frames a grid step holds
    (``block_frames``; one without it).  ``node`` names the graph
    node in the kernel's name."""
    n, h, wdt, d_in = x.shape
    kh, kw, gi, d_out = w.shape
    geo = frame_geometry((h, wdt), (kh, kw), stride, x.dtype.itemsize)
    ho, wo = geo.out_hw
    if gi != d_in:
        assert not im2col, "a grouped conv has no im2col route"
        return group_conv(x, w, stride=stride, bco=bco, bm=bm, node=node)[0]
    if bci is None or bco is None:
        t = select_tile(
            ho * wo, d_in, d_out, rate=rate, dtype_bytes=x.dtype.itemsize
        )
        bci = bci or t.bk
        bco = bco or t.bn
    if im2col is None:
        spec = target_spec()
        frame = conv_frame_vmem_bytes(
            geo, (kh, kw), bci, bco, dtype_bytes=x.dtype.itemsize, spec=spec
        )
        im2col = frame > vmem_budget(spec)
    if im2col:
        geo = conv_geometry((h, wdt), (kh, kw), stride)
        m, k = n * ho * wo, kh * kw * d_in
        y = fcu_matmul_p(
            _im2col(x, geo, (kh, kw)),
            w.reshape(k, d_out),
            bm=fit_bm(m, bm or 512),
            bk=k,
            bn=bco,
            node=node,
        )
        return y.reshape(n, ho, wo, d_out)
    return kpu_conv_p(
        phase_split(x, geo),
        w,
        taps=conv_taps(geo, (kh, kw)),
        out_hw=(ho, wo),
        bci=bci,
        bco=bco,
        frames=block_frames(n, geo, (kh, kw), bci, bco, bm, x.dtype.itemsize),
        wo_mxu=geo.wo_mxu,
        node=node,
    )


def group_conv(
    x: jax.Array,            # [N, H, W, d_in]
    w: jax.Array,            # [kh, kw, d_in / groups, d_out]
    *,
    stride: int = 1,
    bco: Optional[int] = None,
    bm: Optional[int] = None,
    node: Optional[str] = None,
) -> tuple:
    """``kpu_conv``'s grouped route: ``bco`` output channels (whole
    groups; ``group_tile`` without it) a grid step, never im2col.
    Returns the output and ``gpb``, the groups each grid step of the
    built Pallas call holds."""
    n, h, wdt, d_in = x.shape
    kh, kw, gi, d_out = w.shape
    geo = frame_geometry((h, wdt), (kh, kw), stride, x.dtype.itemsize)
    groups = d_in // gi
    if bco is None:
        bco = group_tile(n, geo, (kh, kw), groups, gi, d_out, x.dtype.itemsize)
    bci = bco // (d_out // groups) * gi  # the same groups' inputs
    return kpu_group_conv_p(
        phase_split(x, geo),
        w,
        taps=conv_taps(geo, (kh, kw)),
        out_hw=geo.out_hw,
        bco=bco,
        frames=block_frames(
            n, geo, (kh, kw), bci, bco, bm, x.dtype.itemsize, group_in=gi
        ),
        wo_mxu=geo.wo_mxu,
        node=node,
    )


def group_tile(n, geo, kernel, groups, gi, d_out, dtype_bytes) -> int:
    """Output channels a grid step of the grouped route holds without a
    plan: the fewest whole groups that fill a lane tile and fit VMEM
    (``core.tpu_tiles.groups_per_block``)."""
    spec = target_spec()
    go = d_out // groups

    def fits(gpb):
        return conv_frame_vmem_bytes(
            geo, kernel, gpb * gi, gpb * go, dtype_bytes=dtype_bytes,
            spec=spec, group_in=gi,
        ) <= vmem_budget(spec)

    return go * groups_per_block(groups, gi, go, spec.lanes, fits, spec.lanes)


def block_frames(
    n: int,
    geo: ConvGeometry,
    kernel,
    bci: int,
    bco: int,
    bm: Optional[int],
    dtype_bytes: int,
    group_in: Optional[int] = None,
) -> int:
    """Frames per grid step of the whole-frame route: as many of the
    ``n`` as the pixel tile ``bm`` covers, within the VMEM budget
    (``group_in``: a grouped conv's group width)."""
    spec = target_spec()
    budget = vmem_budget(spec)
    ho, wo = geo.out_hw

    def fits(frames):
        return conv_frame_vmem_bytes(
            geo,
            kernel,
            bci,
            bco,
            dtype_bytes=dtype_bytes,
            spec=spec,
            frames=frames,
            group_in=group_in,
        ) <= budget

    return conv_block_frames(n, ho * wo, bm, fits)


def conv_impl(
    *,
    rate: Optional[Fraction] = None,
    tile: Optional[TileChoice] = None,
    record: Optional[Callable[..., None]] = None,
    node: Optional[str] = None,
):
    """Adapter to the CNN executor's 'conv' signature (models/cnn.py):
    ``impl(x, w_hwio, stride) -> y`` with the KPU kernel underneath.

    ``tile`` pins the channel tiling, the route and the pixel tile to a
    plan's choice (rate-matched path); without it ``rate`` parameterizes
    the uniform search.  ``record(bk=..., bn=..., d_in=..., d_out=...)``
    is called with the executed tile at trace time — plus ``bm`` and
    ``m`` on the im2col route, whose pixel tile the plan pins like the
    FCU kinds', and ``frames`` (per grid step) and ``wo_mxu`` (columns
    each tap window streams) on the whole-frame route.
    ``node`` names the graph node in the kernel's name.  A grouped
    conv (the weight reads fewer channels than ``x`` holds) also
    reports ``groups`` and ``gpb``, the whole groups each grid step of
    the Pallas call it built holds (``group_conv``).
    """
    def impl(x, w, stride):
        if tile is None:
            y = kpu_conv(x, w, stride=stride, rate=rate, node=node)
            if record is not None:
                record(bk=None, bn=None, d_in=x.shape[-1], d_out=w.shape[-1])
            return y
        n, h, wdt, d_in = x.shape
        gi = w.shape[2]
        geo = frame_geometry((h, wdt), w.shape[:2], stride, x.dtype.itemsize)
        if tile.im2col:
            m = n * geo.out_hw[0] * geo.out_hw[1]
            bm = fit_bm(m, tile.bm)
            extra = {"bm": bm, "m": m}
        else:
            bm = tile.bm
            extra = {
                "frames": block_frames(
                    n, geo, w.shape[:2], tile.bk, tile.bn, bm, x.dtype.itemsize,
                    group_in=gi if gi != d_in else None,
                ),
                "wo_mxu": geo.wo_mxu,
            }
        if gi != d_in:
            y, gpb = group_conv(x, w, stride=stride, bco=tile.bn, bm=bm, node=node)
            extra.update(groups=d_in // gi, gpb=gpb)
        else:
            y = kpu_conv(
                x,
                w,
                stride=stride,
                bci=tile.bk,
                bco=tile.bn,
                bm=bm,
                im2col=tile.im2col,
                node=node,
            )
        if record is not None:
            record(
                bk=tile.bk, bn=tile.bn, d_in=x.shape[-1], d_out=w.shape[-1], **extra
            )
        return y

    return impl
