"""jit'd wrapper reshaping [B, H, S, d] <-> [BH, S, d] and choosing blocks."""
from __future__ import annotations

import functools
from typing import Callable, Optional

import jax

from repro.core.tpu_tiles import TileChoice
from .flash_attention import flash_attention_p


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k"))
def flash_attention(
    q: jax.Array,   # [B, H, Sq, d]
    k: jax.Array,   # [B, H, Sk, d]
    v: jax.Array,   # [B, H, Sk, d]
    *,
    causal: bool = True,
    block_q: int = 128,
    block_k: int = 128,
) -> jax.Array:
    b, h, sq, d = q.shape
    sk = k.shape[2]
    out = flash_attention_p(
        q.reshape(b * h, sq, d), k.reshape(b * h, sk, d),
        v.reshape(b * h, sk, d),
        causal=causal, block_q=block_q, block_k=block_k,
    )
    return out.reshape(b, h, sq, d)


def attention_impl(
    *,
    causal: bool = True,
    tile: Optional[TileChoice] = None,
    record: Optional[Callable[..., None]] = None,
):
    """Adapter with the same tile/record protocol as the CNN ops.

    Attention is not part of the CNN LayerGraph, but rate-aware serving
    (benchmarks/rate_aware_serving.py) mixes both worlds; giving every
    kernel adapter one protocol keeps the executed-tile audit uniform.
    ``tile`` maps bm -> block_q and bk -> block_k (the q/k stream tiles);
    ``record(block_q=..., block_k=...)`` reports the executed blocking.
    """
    block_q = tile.bm if tile is not None else 128
    block_k = tile.bk if tile is not None else 128

    def impl(q, k, v):
        y = flash_attention(
            q,
            k,
            v,
            causal=causal,
            block_q=block_q,
            block_k=block_k,
        )
        if record is not None:
            record(block_q=block_q, block_k=block_k, seq=q.shape[2])
        return y

    return impl
