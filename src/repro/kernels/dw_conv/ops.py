"""jit'd wrapper for the depthwise kernel: SAME padding, stride phases +
j-tile choice.

The channel tile (the paper's j, with h = 1 — §II-B: the channel
multiplier replaces d_out for depthwise) is chosen either uniformly
(``_pick_bc`` from one global ``rate``) or per node from a plan-derived
``TileChoice`` (``dw_conv_impl(tile=...)``; ``tile.bk`` is the channel
tile picked by ``core.tpu_tiles.select_tile_for_impl``).  The optional
``record`` callback reports the executed tile back to the caller
(models/cnn.py asserts it against the plan per node).
"""
from __future__ import annotations

import functools
from fractions import Fraction
from typing import Callable, Optional

import jax

from repro.core.tpu_tiles import TileChoice, conv_geometry, plan_dim_tile
from repro.kernels.common import conv_taps, phase_split
from .dw_conv import dw_conv_p


def _pick_bc(c: int, rate: Optional[Fraction]) -> int:
    """The paper's j for depthwise (h=1, cm=1): smallest legal channel
    tile covering the stream rate; default = one lane width."""
    want = 128 if rate is None else max(1, int(rate))
    return plan_dim_tile(c, min(want, c), 128)


@functools.partial(jax.jit, static_argnames=("stride", "rate", "bc"))
def dw_conv(
    x: jax.Array,          # [N, H, W, C]
    w: jax.Array,          # [kh, kw, C]
    *,
    stride: int = 1,
    rate: Optional[Fraction] = None,
    bc: Optional[int] = None,
) -> jax.Array:
    n, h, wdt, c = x.shape
    kh, kw, _ = w.shape
    geo = conv_geometry((h, wdt), (kh, kw), stride)
    return dw_conv_p(
        phase_split(x, geo),
        w,
        taps=conv_taps(geo, (kh, kw)),
        out_hw=geo.out_hw,
        bc=bc or _pick_bc(c, rate),
    )


def dw_conv_impl(
    *,
    rate: Optional[Fraction] = None,
    tile: Optional[TileChoice] = None,
    record: Optional[Callable[..., None]] = None,
):
    """Adapter to the CNN executor's 'dwconv' signature (models/cnn.py).

    The executor stores depthwise weights HWIO with I=1 (grouped-conv
    layout, ``[kh, kw, 1, C]``); the kernel wants ``[kh, kw, C]``.
    ``tile`` pins the channel tile to a plan's choice; ``record`` is
    called with ``bk`` = the executed channel tile (bn is always 1 —
    depthwise has no cross-channel output tiling).
    """
    def impl(x, w, stride):
        if w.shape[-1] != x.shape[-1]:
            raise NotImplementedError(
                f"dw_conv kernel supports channel_multiplier == 1 only "
                f"(got weights for {w.shape[-1]} outputs on "
                f"{x.shape[-1]} channels); use the lax dwconv impl"
            )
        bc = tile.bk if tile is not None else None
        y = dw_conv(x, w[:, :, 0, :], stride=stride, rate=rate, bc=bc)
        if record is not None:
            record(bk=bc, bn=1, d_in=x.shape[-1], d_out=x.shape[-1])
        return y

    return impl
