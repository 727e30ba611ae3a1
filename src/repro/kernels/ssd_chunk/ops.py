"""jit'd wrapper: head broadcast + head-block tiling choice."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.rate import divisors
from .ssd_chunk import ssd_chunk_p


@functools.partial(jax.jit, static_argnames=("chunk", "head_block"))
def ssd_chunk(
    x,
    dt,
    a,
    b,
    c,
    *,
    chunk: int = 128,
    head_block: int | None = None,
):
    """x: [B,L,H,P]; dt: [B,L,H]; a: [H]; b,c: [B,L,G,N] (G | H)."""
    h = x.shape[2]
    g = b.shape[2]
    if g != h:
        b = jnp.repeat(b, h // g, axis=2)
        c = jnp.repeat(c, h // g, axis=2)
    if head_block is None:
        head_block = max(d for d in divisors(h) if d <= 8)
    return ssd_chunk_p(x, dt, a, b, c, chunk=chunk, head_block=head_block)
