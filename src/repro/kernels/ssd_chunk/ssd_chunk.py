"""Fused SSD chunk kernel — the Pallas answer to §Perf Cell B's residual.

The pure-JAX SSD (even the streaming form) materializes per-chunk decay
masks, scores and state tensors in HBM: zamba2/mamba2 prefill is
memory-bound on exactly those buffers.  This kernel runs one (batch,
head-block, chunk) cell per grid step and keeps every intermediate —
L-mask, CB^T scores, chunk states — in VMEM; only x/dt/B/C tiles stream
in and y tiles stream out.  The sequential inter-chunk recurrence rides
the innermost grid dimension with the running state held in a VMEM
scratch accumulator (same pattern as the FCU's C-step accumulation: the
paper's weight-reconfiguration loop, state edition).

Grid: (B, H_blocks, n_chunks) — n_chunks innermost/sequential.
Blocks per step:
  x  [1, Q, hb, P]   dt [1, Q, hb]   b/c [1, Q, hb, N]  (pre-broadcast)
  y  [1, Q, hb, P]   scratch: state [hb, P, N] f32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import pallas_call


def _ssd_kernel(
    x_ref,
    dt_ref,
    a_ref,
    b_ref,
    c_ref,
    o_ref,
    s_ref,
    state_ref,
    *,
    n_chunks: int,
    q: int,
):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0, 0].astype(jnp.float32)     # [Q, hb, P]
    dt = dt_ref[0, 0].astype(jnp.float32)   # [Q, hb]
    a = a_ref[...].astype(jnp.float32)      # [hb]
    b = b_ref[0, 0].astype(jnp.float32)     # [Q, hb, N]
    c = c_ref[0, 0].astype(jnp.float32)     # [Q, hb, N]

    ad = dt * a[None, :]                    # [Q, hb]
    xd = x * dt[..., None]                  # [Q, hb, P]
    a_cum = jnp.cumsum(ad, axis=0)          # [Q, hb]

    # intra-chunk: y_diag[i] = sum_{j<=i} exp(acum_i - acum_j) (c_i.b_j) xd_j
    diff = a_cum[:, None, :] - a_cum[None, :, :]          # [Qi, Qj, hb]
    tri = jnp.tril(jnp.ones((q, q), jnp.bool_))
    lmask = jnp.where(tri[..., None], jnp.exp(diff), 0.0)
    scores = jnp.einsum("ihn,jhn->ijh", c, b)             # [Qi, Qj, hb]
    y = jnp.einsum("ijh,jhp->ihp", scores * lmask, xd)

    # inter-chunk: contribution of the carried state
    s_prev = state_ref[...]                               # [hb, P, N]
    y += jnp.einsum("ihn,hpn->ihp", c * jnp.exp(a_cum)[..., None], s_prev)

    # state update: s = exp(sum ad) * s_prev + sum_j exp(acum_Q - acum_j) b_j xd_j
    decay_end = jnp.exp(a_cum[-1, :][None, :] - a_cum)    # [Q, hb]
    s_new = jnp.exp(a_cum[-1, :])[:, None, None] * s_prev + jnp.einsum(
        "jhn,jh,jhp->hpn", b, decay_end, xd
    )
    state_ref[...] = s_new

    o_ref[0, 0] = y.astype(o_ref.dtype)

    @pl.when(ci == n_chunks - 1)
    def _emit_state():
        s_ref[0] = s_new.astype(s_ref.dtype)


def ssd_chunk_p(
    x: jax.Array,    # [B, L, H, P]
    dt: jax.Array,   # [B, L, H]
    a: jax.Array,    # [H]
    b: jax.Array,    # [B, L, H, N]  (head-broadcast done by ops.py)
    c: jax.Array,    # [B, L, H, N]
    *,
    chunk: int,
    head_block: int = 8,
):
    bsz, l, h, p = x.shape
    n = b.shape[-1]
    assert l % chunk == 0 and h % head_block == 0, (l, chunk, h, head_block)
    nc = l // chunk
    grid = (bsz, h // head_block, nc)
    xc = x.reshape(bsz, nc, chunk, h, p)
    dtc = dt.reshape(bsz, nc, chunk, h)
    bc = b.reshape(bsz, nc, chunk, h, n)
    cc = c.reshape(bsz, nc, chunk, h, n)

    y, s = pallas_call(
        functools.partial(_ssd_kernel, n_chunks=nc, q=chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec(
                (1, 1, chunk, head_block, p), lambda bb, hb, ci: (bb, ci, 0, hb, 0)
            ),
            pl.BlockSpec(
                (1, 1, chunk, head_block), lambda bb, hb, ci: (bb, ci, 0, hb)
            ),
            pl.BlockSpec((head_block,), lambda bb, hb, ci: (hb,)),
            pl.BlockSpec(
                (1, 1, chunk, head_block, n), lambda bb, hb, ci: (bb, ci, 0, hb, 0)
            ),
            pl.BlockSpec(
                (1, 1, chunk, head_block, n), lambda bb, hb, ci: (bb, ci, 0, hb, 0)
            ),
        ],
        out_specs=[
            pl.BlockSpec(
                (1, 1, chunk, head_block, p), lambda bb, hb, ci: (bb, ci, 0, hb, 0)
            ),
            pl.BlockSpec((1, head_block, p, n), lambda bb, hb, ci: (bb, hb, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, nc, chunk, h, p), x.dtype),
            jax.ShapeDtypeStruct((bsz, h, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((head_block, p, n), jnp.float32)],
    )(xc, dtc, a, bc, cc)
    return y.reshape(bsz, l, h, p), s
