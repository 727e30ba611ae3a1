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
padded input, or — when that frame cannot fit VMEM (the lane-sparse
3-channel stems; ``TileChoice.im2col``, or the same budget check on the
uniform path) — im2col patches through the FCU matmul kernel.
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
    conv_frame_vmem_bytes,
    conv_geometry,
    fit_bm,
    select_tile,
    vmem_budget,
)
from repro.kernels.common import conv_taps, phase_split
from repro.kernels.fcu_matmul.fcu_matmul import fcu_matmul_p
from .kpu_conv import kpu_conv_p


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
    jax.jit, static_argnames=("stride", "rate", "bci", "bco", "bm", "im2col")
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
) -> jax.Array:
    """``im2col=None`` picks the route by the frame's VMEM working set;
    on the im2col route ``bm`` is the pixel tile of the FCU matmul."""
    n, h, wdt, d_in = x.shape
    kh, kw, _, d_out = w.shape
    geo = conv_geometry((h, wdt), (kh, kw), stride)
    ho, wo = geo.out_hw
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
        m, k = n * ho * wo, kh * kw * d_in
        y = fcu_matmul_p(
            _im2col(x, geo, (kh, kw)),
            w.reshape(k, d_out),
            bm=fit_bm(m, bm or 512),
            bk=k,
            bn=bco,
        )
        return y.reshape(n, ho, wo, d_out)
    return kpu_conv_p(
        phase_split(x, geo),
        w,
        taps=conv_taps(geo, (kh, kw)),
        out_hw=(ho, wo),
        bci=bci,
        bco=bco,
    )


def conv_impl(
    *,
    rate: Optional[Fraction] = None,
    tile: Optional[TileChoice] = None,
    record: Optional[Callable[..., None]] = None,
):
    """Adapter to the CNN executor's 'conv' signature (models/cnn.py):
    ``impl(x, w_hwio, stride) -> y`` with the KPU kernel underneath.

    ``tile`` pins the channel tiling and the route to a plan's choice
    (rate-matched path); without it ``rate`` parameterizes the uniform
    search.  ``record(bk=..., bn=..., d_in=..., d_out=...)`` is called
    with the executed tile at trace time — plus ``bm`` and ``m`` on the
    im2col route, whose pixel tile the plan pins like the FCU kinds'.
    """
    def impl(x, w, stride):
        if tile is None:
            y = kpu_conv(x, w, stride=stride, rate=rate)
            if record is not None:
                record(bk=None, bn=None, d_in=x.shape[-1], d_out=w.shape[-1])
            return y
        extra = {}
        if tile.im2col:
            n, h, wdt, _ = x.shape
            ho, wo = conv_geometry((h, wdt), w.shape[:2], stride).out_hw
            m = n * ho * wo
            extra = {"bm": fit_bm(m, tile.bm), "m": m}
        y = kpu_conv(
            x,
            w,
            stride=stride,
            bci=tile.bk,
            bco=tile.bn,
            bm=extra.get("bm"),
            im2col=tile.im2col,
        )
        if record is not None:
            record(
                bk=tile.bk, bn=tile.bn, d_in=x.shape[-1], d_out=w.shape[-1], **extra
            )
        return y

    return impl
