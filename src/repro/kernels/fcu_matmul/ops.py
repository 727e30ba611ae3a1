"""jit'd public wrapper: DSE-derived tiling + shape plumbing.

``fcu_matmul`` is the drop-in for pointwise convolutions and dense layers
(flattens leading dims to the pixel/m axis).  The BlockSpec tiling comes
from the paper's HJ exploration, two ways:

  * uniform — ``core.tpu_tiles.select_tile`` with one (optional) global
    stream ``rate`` shared by every layer;
  * rate-matched — ``pointwise_impl(tile=...)`` / ``dense_impl(tile=...)``
    receive one node's plan-derived ``TileChoice``
    (``GraphPlan.kernel_plan``) and execute exactly that (bk, bn); the
    pixel tile bm re-fits the runtime m via ``fit_bm`` (batch and spatial dims are
    flattened together, so m varies with batch while bk/bn do not) —
    unless the plan was pinned to a serving batch
    (``kernel_plan(batch=B)``), in which case the planned bm *divides*
    the runtime m and the re-fit is the identity.  The optional
    ``record`` callback reports the executed tile back to the caller
    (models/cnn.py asserts it against the plan per node, including bm on
    the batch-pinned path).
"""

from __future__ import annotations

import functools
from fractions import Fraction
from typing import Callable, Optional

import jax

from repro.core.tpu_tiles import TileChoice, fit_bm, select_tile
from .fcu_matmul import fcu_matmul_p


@functools.partial(jax.jit, static_argnames=("rate", "bm", "bk", "bn", "node"))
def fcu_matmul(
    x: jax.Array,
    w: jax.Array,
    *,
    rate: Optional[Fraction] = None,
    bm: Optional[int] = None,
    bk: Optional[int] = None,
    bn: Optional[int] = None,
    node: Optional[str] = None,
) -> jax.Array:
    """x: [..., d_in] @ w: [d_in, d_out] -> [..., d_out]; ``node`` names
    the graph node in the kernel's name."""
    lead = x.shape[:-1]
    d_in = x.shape[-1]
    d_out = w.shape[-1]
    m = 1
    for s in lead:
        m *= s
    xm = x.reshape(m, d_in)
    if bm is None or bk is None or bn is None:
        t = select_tile(m, d_in, d_out, rate=rate, dtype_bytes=x.dtype.itemsize)
        bk = bk or t.bk
        bn = bn or t.bn
        bm = bm or fit_bm(m, t.bm)
    else:
        bm = fit_bm(m, bm)
    out = fcu_matmul_p(xm, w, bm=bm, bk=bk, bn=bn, node=node)
    return out.reshape(*lead, d_out)


def _fcu_impl(
    rate: Optional[Fraction],
    tile: Optional[TileChoice],
    record: Optional[Callable[..., None]],
    node: Optional[str],
):
    def impl(x, w):
        if tile is None:
            return fcu_matmul(x, w, rate=rate, node=node)
        m = 1
        for s in x.shape[:-1]:
            m *= s
        bm = fit_bm(m, tile.bm)
        y = fcu_matmul(x, w, bm=bm, bk=tile.bk, bn=tile.bn, node=node)
        if record is not None:
            record(
                bk=tile.bk,
                bn=tile.bn,
                bm=bm,
                d_in=x.shape[-1],
                d_out=w.shape[-1],
                m=m,
            )
        return y

    return impl


def pointwise_impl(
    *,
    rate: Optional[Fraction] = None,
    tile: Optional[TileChoice] = None,
    record: Optional[Callable[..., None]] = None,
    node: Optional[str] = None,
):
    """Adapter to the CNN executor's 'pointwise' signature (models/cnn.py):
    a 1x1 conv is exactly the FCU matmul over the pixel axis."""
    return _fcu_impl(rate, tile, record, node)


def dense_impl(
    *,
    rate: Optional[Fraction] = None,
    tile: Optional[TileChoice] = None,
    record: Optional[Callable[..., None]] = None,
    node: Optional[str] = None,
):
    """Adapter to the CNN executor's 'dense' signature (models/cnn.py)."""
    return _fcu_impl(rate, tile, record, node)
