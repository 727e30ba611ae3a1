"""jit'd wrapper for the squeeze-and-excitation gate kernel: the block
choice and the gate's layout.

The block (rows x channel tile) comes from ``core.tpu_tiles.scale_tile``,
either from the node's plan (``se_scale_impl(tile=...)``: ``tile.bk`` is
the channel tile, ``tile.bm`` the pixels of one block) or, without a
plan, from the channel count alone.  The gate [N, C] is passed to the
kernel as [N, 1, C], so that its block [1, 1, bc] is legal and the
kernel's operands match no other kernel's layout.  ``record`` reports the
executed tile back to the caller (models/cnn.py asserts it against the
plan per node).
"""
from __future__ import annotations

import functools
from typing import Callable, Optional

import jax

from repro.core.hw_specs import target_spec
from repro.core.tpu_tiles import TileChoice, scale_tile
from .se_scale import se_scale_p


@functools.partial(jax.jit, static_argnames=("bh", "bc", "node"))
def se_scale(
    x: jax.Array,  # [N, H, W, C]
    g: jax.Array,  # [N, C]
    *,
    bh: Optional[int] = None,
    bc: Optional[int] = None,
    node: Optional[str] = None,
) -> jax.Array:
    n, h, w, c = x.shape
    if bh is None or bc is None:
        fit_bh, fit_bc, _ = scale_tile(
            (h, w), c, bc or 1, dtype_bytes=x.dtype.itemsize, spec=target_spec()
        )
        bh, bc = bh or fit_bh, bc or fit_bc
    return se_scale_p(x, g.reshape(n, 1, c), bh=bh, bc=bc, node=node)


def se_scale_impl(
    *,
    rate=None,
    tile: Optional[TileChoice] = None,
    record: Optional[Callable[..., None]] = None,
    node: Optional[str] = None,
):
    """Adapter to the CNN executor's 'scale' signature (models/cnn.py):
    ``impl(x, gate)``.  ``rate`` is accepted for the uniform path's
    signature and unused: the block depends on the shapes alone."""

    def impl(x, g):
        bh = bc = None
        if tile is not None:
            bh, bc = tile.bm // x.shape[2], tile.bk
        y = se_scale(x, g, bh=bh, bc=bc, node=node)
        if record is not None:
            record(bk=bc, bn=1, bm=bh and bh * x.shape[2],
                   d_in=x.shape[-1], d_out=x.shape[-1])
        return y

    return impl
