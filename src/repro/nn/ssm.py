"""Mamba2 / SSD (state-space duality) blocks [arXiv:2405.21060].

Chunked SSD algorithm (paper Listing 1 adapted to JAX):
  within-chunk: quadratic "attention-like" term with the 1-semiseparable
  decay mask  L[i,j] = exp(sum_{j<m<=i} a_m);
  cross-chunk: per-chunk final states carried by a (sequential) lax.scan
  — the recurrence is linear, and chunk count is small (S/256), so a
  sequential scan is the right TPU trade (matches the Mamba2 reference).

Decode path: the dual recurrent form, one state update per token:
  S' = exp(dt*A) * S + dt * B x^T ;  y = C S' + D x.

Shapes follow the Mamba2 convention:
  x  : [B, L, H, P]   (H heads, P head dim; d_inner = H*P)
  dt : [B, L, H]
  B,C: [B, L, G, N]   (G groups, N state dim; broadcast G -> H)
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .layers import _dense_init


@dataclasses.dataclass(frozen=True)
class SSMSpec:
    d_model: int
    d_state: int = 128
    d_conv: int = 4
    expand: int = 2
    head_dim: int = 64
    n_groups: int = 1
    chunk: int = 128
    streaming: bool = True   # scan-over-chunks SSD (continuous-flow form)
    seq_parallel: bool = True  # shard the scan over the 'model' mesh axis

    @property
    def d_inner(self) -> int:
        return self.expand * self.d_model

    @property
    def n_heads(self) -> int:
        return self.d_inner // self.head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.n_groups * self.d_state


def init_ssm(rng, spec: SSMSpec, dtype=jnp.float32) -> dict:
    ks = jax.random.split(rng, 6)
    d, di = spec.d_model, spec.d_inner
    proj_out = 2 * di + 2 * spec.n_groups * spec.d_state + spec.n_heads
    dt = jnp.exp(jax.random.uniform(ks[2], (spec.n_heads,), jnp.float32)
                 * (math.log(0.1) - math.log(0.001)) + math.log(0.001))
    return {
        "in_proj": _dense_init(ks[0], (d, proj_out), dtype),
        "conv_w": (jax.random.normal(ks[1], (spec.d_conv, spec.conv_dim),
                                     jnp.float32)
                   / math.sqrt(spec.d_conv)).astype(dtype),
        "conv_b": jnp.zeros((spec.conv_dim,), dtype),
        "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(jnp.float32),
        "a_log": jnp.log(jnp.arange(1, spec.n_heads + 1, dtype=jnp.float32)),
        "d_skip": jnp.ones((spec.n_heads,), jnp.float32),
        "out_proj": _dense_init(ks[3], (di, d), dtype),
        "norm_scale": jnp.ones((di,), dtype),
    }


def _segsum(a):
    """a: [..., T] -> [..., T, T] lower-tri cumulative sums (exclusive)."""
    t = a.shape[-1]
    cum = jnp.cumsum(a, axis=-1)
    diff = cum[..., :, None] - cum[..., None, :]
    mask = jnp.tril(jnp.ones((t, t), bool), k=0)
    return jnp.where(mask, diff, -jnp.inf)


def ssd_chunked_streaming(x, dt, a, b, c, *, chunk: int):
    """SSD as a *streaming scan over chunks* — the continuous-flow form.

    The vectorized form (`ssd_chunked`) materializes every chunk's decay
    mask / scores simultaneously: [B, H, nc, Q, Q] alone is ~2 GiB/device
    at zamba2 prefill_32k, and the measured HBM roofline term is dominated
    by those buffers.  Scanning chunk-by-chunk (the state recurrence is
    sequential anyway) keeps per-chunk tensors transient and fusable:
    measured bytes drop ~2x at identical FLOPs and numerics
    (tests/models/test_nn_consistency.py covers equality).
    """
    bsz, l, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    nc = l // chunk
    rep = h // g
    q = chunk

    ad = (dt * a[None, None, :]).reshape(bsz, nc, q, h)       # [B,nc,Q,H]
    xd = (x * dt[..., None]).reshape(bsz, nc, q, h, p)
    # B/C stay at group width [*, G, N]: repeating to H heads over the full
    # sequence materializes rep x (32x for zamba2, 48x for mamba2) the
    # tensor — measured as the dominant HBM term.  The head broadcast
    # happens per chunk inside the scan step (transient, fusable).
    bc = b.reshape(bsz, nc, q, g, n)
    cc = c.reshape(bsz, nc, q, g, n)

    tri = jnp.tril(jnp.ones((q, q), bool))

    def step(s_prev, inp):
        ad_i, x_i, b_g, c_g = inp          # [B,Q,H], [B,Q,H,P], [B,Q,G,N] x2
        b_i = jnp.repeat(b_g, rep, axis=2)                    # [B,Q,H,N]
        c_i = jnp.repeat(c_g, rep, axis=2)
        a_cum = jnp.cumsum(ad_i, axis=1)                      # [B,Q,H]
        diff = a_cum[:, :, None, :] - a_cum[:, None, :, :]    # [B,Qi,Qj,H]
        lmask = jnp.where(tri[None, :, :, None], jnp.exp(diff), 0.0)
        scores = jnp.einsum("bihs,bjhs->bijh", c_i.astype(jnp.float32),
                            b_i.astype(jnp.float32))
        y_diag = jnp.einsum("bijh,bjhp->bihp", scores * lmask,
                            x_i.astype(jnp.float32))
        decay_to_end = jnp.exp(a_cum[:, -1:, :] - a_cum)      # [B,Q,H]
        s_new = (s_prev * jnp.exp(a_cum[:, -1, :])[..., None, None]
                 + jnp.einsum("bqhs,bqh,bqhp->bhps",
                              b_i.astype(jnp.float32), decay_to_end,
                              x_i.astype(jnp.float32)))
        y_off = jnp.einsum("bqhs,bqh,bhps->bqhp", c_i.astype(jnp.float32),
                           jnp.exp(a_cum), s_prev)
        return s_new, y_diag + y_off

    s0 = jnp.zeros((bsz, h, p, n), jnp.float32)
    xs = (ad.transpose(1, 0, 2, 3), xd.transpose(1, 0, 2, 3, 4),
          bc.transpose(1, 0, 2, 3, 4), cc.transpose(1, 0, 2, 3, 4))
    s_final, ys = jax.lax.scan(step, s0, xs)
    y = ys.transpose(1, 0, 2, 3, 4).reshape(bsz, l, h, p)
    return y, s_final


def _ambient_mesh():
    from jax._src import mesh as mesh_lib
    m = mesh_lib.thread_resources.env.physical_mesh
    return None if (m.empty or not m.axis_names) else m


def ssd_seq_parallel(x, dt, a, b, c, *, chunk: int, mesh, axis: str = "model"):
    """Sequence-parallel SSD over the 'model' mesh axis.

    Without this, a seq-sharded residual stream must be ALL-GATHERED at
    every SSM layer (the chunk recurrence runs over the whole sequence) —
    measured 23 GiB/device of all-gathers at zamba2 prefill_32k.  Instead:

      1. each shard runs the streaming chunk scan on its LOCAL sequence
         slice from a zero state -> (y0, s_loc);
      2. shards exchange tiny per-shard summaries (final local state s_loc
         [B,H,P,N] and total decay D [B,H]) via one all_gather (~MBs);
      3. each shard computes its true incoming state s_in by the K-term
         prefix recurrence locally and corrects its outputs:
         y += C * exp(a_cum) * s_in.

    Exact (linear recurrence), tested against the single-shard form.
    """
    from jax.sharding import PartitionSpec as P
    da = tuple(ax for ax in ("pod", "data") if ax in mesh.axis_names)
    bspec = da if da else None

    def local(xl, dtl, al, bl, cl):
        y0, s_loc = ssd_chunked_streaming(xl, dtl, al, bl, cl, chunk=chunk)
        ad = dtl * al[None, None, :]
        acum = jnp.cumsum(ad, axis=1)                 # [B, Lloc, H]
        d_shard = jnp.exp(acum[:, -1, :])             # [B, H]
        gs = jax.lax.all_gather(s_loc, axis)          # [K, B, H, P, N]
        gd = jax.lax.all_gather(d_shard, axis)        # [K, B, H]
        kk = jax.lax.axis_index(axis)
        n_sh = gs.shape[0]

        def fbody(j, carry):
            s_run, s_in = carry
            s_in = jnp.where(j == kk, s_run, s_in)
            s_run = gs[j] + gd[j][..., None, None] * s_run
            return (s_run, s_in)

        s_fin, s_in = jax.lax.fori_loop(
            0, n_sh, fbody, (jnp.zeros_like(s_loc), jnp.zeros_like(s_loc)))
        h = xl.shape[2]
        repf = h // cl.shape[2]
        c_h = jnp.repeat(cl, repf, axis=2)            # [B, Lloc, H, N]
        y = y0 + jnp.einsum("blhs,blh,bhps->blhp",
                            c_h.astype(jnp.float32), jnp.exp(acum), s_in)
        return y, s_fin

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(bspec, axis, None, None), P(bspec, axis, None), P(None),
                  P(bspec, axis, None, None), P(bspec, axis, None, None)),
        out_specs=(P(bspec, axis, None, None), P(bspec, None, None, None)),
        check_vma=False,
    )
    return fn(x, dt, a, b, c)


def ssd_chunked(x, dt, a, b, c, *, chunk: int):
    """SSD scan.  x: [B, L, H, P]; dt: [B, L, H]; a: [H] (negative);
    b, c: [B, L, G, N].  Returns y: [B, L, H, P], final_state [B, H, P, N].
    L must be a multiple of ``chunk`` (models pad)."""
    bsz, l, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    nc = l // chunk
    rep = h // g

    ad = dt * a[None, None, :]                                # [B, L, H]
    xd = x * dt[..., None]
    # chunked views
    adc = ad.reshape(bsz, nc, chunk, h).transpose(0, 3, 1, 2)  # [B,H,nc,Q]
    xc = xd.reshape(bsz, nc, chunk, h, p)
    bc = b.reshape(bsz, nc, chunk, g, n)
    cc = c.reshape(bsz, nc, chunk, g, n)
    bch = jnp.repeat(bc, rep, axis=3)                          # [B,nc,Q,H,N]
    cch = jnp.repeat(cc, rep, axis=3)

    # ---- intra-chunk (quadratic, attention-like) ----
    lmask = jnp.exp(_segsum(adc))                              # [B,H,nc,Q,Q]
    scores = jnp.einsum("bnihs,bnjhs->bhnij", cch.astype(jnp.float32),
                        bch.astype(jnp.float32))
    y_diag = jnp.einsum("bhnij,bnjhp->bnihp",
                        scores * lmask.transpose(0, 1, 2, 3, 4),
                        xc.astype(jnp.float32))

    # ---- chunk states ----
    a_cum = jnp.cumsum(adc, axis=-1)                           # [B,H,nc,Q]
    decay_to_end = jnp.exp(a_cum[..., -1:] - a_cum)            # [B,H,nc,Q]
    states = jnp.einsum("bnqhs,bhnq,bnqhp->bnhps",
                        bch.astype(jnp.float32),
                        decay_to_end, xc.astype(jnp.float32))  # [B,nc,H,P,N]

    # ---- inter-chunk recurrence (sequential scan over nc chunks) ----
    chunk_decay = jnp.exp(a_cum[..., -1])                      # [B,H,nc]

    def step(s_prev, inp):
        st, dec = inp                                          # [B,H,P,N],[B,H]
        s_new = s_prev * dec[..., None, None] + st
        return s_new, s_prev

    states_t = states.transpose(1, 0, 2, 3, 4)                 # [nc,B,H,P,N]
    decay_t = chunk_decay.transpose(2, 0, 1)                   # [nc,B,H]
    s0 = jnp.zeros((bsz, h, p, n), jnp.float32)
    s_final, s_prev_all = jax.lax.scan(step, s0, (states_t, decay_t))
    s_prev = s_prev_all.transpose(1, 0, 2, 3, 4)               # [B,nc,H,P,N]

    # ---- state -> output within chunk ----
    in_decay = jnp.exp(a_cum)                                  # [B,H,nc,Q]
    y_off = jnp.einsum("bnqhs,bhnq,bnhps->bnqhp",
                       cch.astype(jnp.float32), in_decay, s_prev)

    y = (y_diag + y_off).reshape(bsz, l, h, p)
    return y, s_final


def ssm_forward(
    params: dict,
    u: jax.Array,                 # [B, L, d_model]
    spec: SSMSpec,
    *,
    state: Optional[Tuple[jax.Array, jax.Array]] = None,  # (ssm [B,H,P,N], conv [B,K-1,convdim])
    decode: bool = False,
):
    """Returns (y [B, L, d_model], new_state).  decode=True requires L==1."""
    bsz, l, _ = u.shape
    h, p, n, g = spec.n_heads, spec.head_dim, spec.d_state, spec.n_groups
    di = spec.d_inner

    proj = u @ params["in_proj"]
    # split: [d_inner gate | conv_dim (x,B,C) | n_heads dt]
    z = proj[..., :di]
    xbc = proj[..., di:di + spec.conv_dim]
    dt_raw = proj[..., di + spec.conv_dim:]

    # causal depthwise conv over time
    k = spec.d_conv
    if decode:
        conv_cache = state[1]                        # [B, K-1, convdim]
        window = jnp.concatenate([conv_cache, xbc], axis=1)   # [B, K, convdim]
        conv_out = jnp.einsum("bkc,kc->bc", window.astype(jnp.float32),
                              params["conv_w"].astype(jnp.float32))
        conv_out = (conv_out + params["conv_b"].astype(jnp.float32))[:, None]
        new_conv = window[:, 1:]
    else:
        pad = jnp.zeros((bsz, k - 1, spec.conv_dim), xbc.dtype)
        xpad = jnp.concatenate([pad, xbc], axis=1)
        idx = jnp.arange(l)[:, None] + jnp.arange(k)[None, :]
        win = xpad[:, idx]                           # [B, L, K, convdim]
        conv_out = jnp.einsum("blkc,kc->blc", win.astype(jnp.float32),
                              params["conv_w"].astype(jnp.float32))
        conv_out = conv_out + params["conv_b"].astype(jnp.float32)
        new_conv = xpad[:, -(k - 1):]
    xbc = jax.nn.silu(conv_out)

    xs = xbc[..., :di].reshape(bsz, l, h, p)
    bmat = xbc[..., di:di + g * n].reshape(bsz, l, g, n)
    cmat = xbc[..., di + g * n:].reshape(bsz, l, g, n)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32)
                         + params["dt_bias"][None, None, :])   # [B, L, H]
    a = -jnp.exp(params["a_log"])                              # [H] negative

    if decode:
        s_prev = state[0]                                      # [B,H,P,N]
        ad = jnp.exp(dt[:, 0, :] * a[None, :])                 # [B,H]
        # broadcast B/C groups to heads
        bg = jnp.repeat(bmat[:, 0], h // g, axis=1)            # [B,H,N]
        cg = jnp.repeat(cmat[:, 0], h // g, axis=1)
        bx = jnp.einsum("bhp,bhn,bh->bhpn", xs[:, 0].astype(jnp.float32),
                        bg.astype(jnp.float32), dt[:, 0])
        s_new = s_prev * ad[..., None, None] + bx
        y = jnp.einsum("bhn,bhpn->bhp", cg.astype(jnp.float32), s_new)
        y = y + params["d_skip"][None, :, None] * xs[:, 0].astype(jnp.float32)
        y = y.reshape(bsz, 1, di)
        new_state = (s_new, new_conv)
    else:
        pad_to = (-l) % spec.chunk
        if pad_to:
            xs = jnp.pad(xs, ((0, 0), (0, pad_to), (0, 0), (0, 0)))
            dt = jnp.pad(dt, ((0, 0), (0, pad_to), (0, 0)))
            bmat = jnp.pad(bmat, ((0, 0), (0, pad_to), (0, 0), (0, 0)))
            cmat = jnp.pad(cmat, ((0, 0), (0, pad_to), (0, 0), (0, 0)))
        mesh = _ambient_mesh()
        lp = xs.shape[1]
        if (spec.seq_parallel and mesh is not None
                and "model" in mesh.axis_names):
            n_model = dict(zip(mesh.axis_names, mesh.devices.shape))["model"]
            seq_ok = (lp % (n_model * spec.chunk) == 0) and n_model > 1
        else:
            seq_ok = False
        if seq_ok:
            y, s_final = ssd_seq_parallel(xs, dt, a, bmat, cmat,
                                          chunk=spec.chunk, mesh=mesh)
        else:
            ssd = ssd_chunked_streaming if spec.streaming else ssd_chunked
            y, s_final = ssd(xs, dt, a, bmat, cmat, chunk=spec.chunk)
        y = y[:, :l]
        y = y + params["d_skip"][None, None, :, None] * xs[:, :l].astype(jnp.float32)
        y = y.reshape(bsz, l, di)
        new_state = (s_final, new_conv)

    # gated RMSNorm (mamba2's norm-before-out-proj)
    yz = y * jax.nn.silu(z.astype(jnp.float32))
    var = jnp.mean(jnp.square(yz), axis=-1, keepdims=True)
    yz = yz * jax.lax.rsqrt(var + 1e-6) * params["norm_scale"].astype(jnp.float32)
    out = yz.astype(u.dtype) @ params["out_proj"]
    return out, new_state


def init_ssm_state(bsz: int, spec: SSMSpec, dtype=jnp.float32):
    return (
        jnp.zeros((bsz, spec.n_heads, spec.head_dim, spec.d_state), jnp.float32),
        jnp.zeros((bsz, spec.d_conv - 1, spec.conv_dim), dtype),
    )
