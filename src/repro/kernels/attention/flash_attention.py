"""Blockwise (flash-style) causal attention — Pallas TPU kernel.

The LM-serving face of continuous flow: the KV stream is consumed in
VMEM-sized blocks with an online-softmax running state, so the unit never
waits for the full score matrix — the attention analogue of the FCU's
C-step accumulation.  Tiling (block_q = multi-pixel P over query
positions; block_k = the j-tile over the KV stream) follows the same
divisor constraints.

Grid: (batch*heads, q_blocks, k_blocks); k innermost so the running
(m, l, acc) scratch carries across KV blocks of one query block.
Causal masking skips fully-masked KV blocks' contribution via masking
(block skipping is a grid-level optimization left to the serving layer).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import pallas_call

_NEG_INF = -1e30


def _flash_kernel(
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    m_ref,
    l_ref,
    acc_ref,
    *,
    scale: float,
    causal: bool,
    block_q: int,
    block_k: int,
    grid_k: int,
):
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]                               # [bq, d]
    k = k_ref[0]                               # [bk, d]
    v = v_ref[0]                               # [bk, d]
    qk = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    s = qk * scale  # [bq, bk]

    if causal:
        qi = pl.program_id(1)
        iota_q = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        iota_k = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        q_pos = qi * block_q + iota_q
        k_pos = kb * block_k + iota_k
        s = jnp.where(q_pos >= k_pos, s, _NEG_INF)

    m_prev = m_ref[...]                        # [bq, 1]
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)                     # [bq, bk]
    alpha = jnp.exp(m_prev - m_new)            # [bq, 1]
    l_new = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
    pv = jax.lax.dot_general(
        p,
        v.astype(jnp.float32),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    acc_ref[...] = acc_ref[...] * alpha + pv
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(kb == grid_k - 1)
    def _flush():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention_p(
    q: jax.Array,   # [BH, Sq, d]
    k: jax.Array,   # [BH, Sk, d]
    v: jax.Array,   # [BH, Sk, d]
    *,
    causal: bool = True,
    block_q: int = 128,
    block_k: int = 128,
    scale: float | None = None,
) -> jax.Array:
    bh, sq, d = q.shape
    _, sk, _ = k.shape
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    assert sq % block_q == 0 and sk % block_k == 0, (sq, sk, block_q, block_k)
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    grid = (bh, sq // block_q, sk // block_k)
    return pallas_call(
        functools.partial(
            _flash_kernel,
            scale=scale,
            causal=causal,
            block_q=block_q,
            block_k=block_k,
            grid_k=grid[2],
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
    )(q, k, v)
