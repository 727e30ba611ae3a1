"""Plumbing shared by the kernels: backend-decided Pallas calls and the
stride-phase split of a conv input.

``pallas_call`` decides where a kernel runs, in one place and at
lowering time, from the platform the computation is compiled for
(``jax.lax.platform_dependent``): lowered for a TPU it is the Mosaic
kernel, with the target spec's ``vmem_bytes`` as its VMEM limit;
lowered for anything else it is the Pallas interpreter.  A kernel
served on a TPU therefore never falls back to the interpreter, the same
program runs on a CPU test host, and a compile for a described but
unattached TPU (tests/kernels/test_tpu_compile.py) gets the Mosaic
kernel too.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.hw_specs import target_spec
from repro.core.tpu_tiles import ConvGeometry


def kernel_name(kind: str, node: Optional[str] = None) -> str:
    """A kernel call's stable name: its kind, then the graph node it
    serves (``kpu_conv.l1b1_conv1``), or the kind alone."""
    return kind if node is None else f"{kind}.{node}"


def pallas_call(kernel, *, name: str, **kwargs):
    """``pl.pallas_call(kernel, name=name, **kwargs)`` whose interpret
    mode and VMEM limit are decided by the backend; returns
    ``f(*operands)``.  ``name`` (``kernel_name``) names the Mosaic call
    in the compiled program, so that a trace finds it after a refactor."""

    def call(*operands):
        compiled = pl.pallas_call(
            kernel,
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=target_spec().vmem_bytes
            ),
            name=name,
            **kwargs,
        )
        interpreted = pl.pallas_call(kernel, interpret=True, name=name, **kwargs)
        return jax.lax.platform_dependent(
            *operands, tpu=compiled, default=interpreted
        )

    return call


def phase_split(x: jax.Array, geo: ConvGeometry) -> jax.Array:
    """[N, H, W, C] -> [N, P, Hq, Wq, C]: SAME-pad, then keep the stride
    phases some tap reads (``core.tpu_tiles.conv_geometry``).  Tap
    ``(dy, dx)`` then reads the contiguous window ``geo.tap(dy, dx)``."""
    xp = jnp.pad(x, ((0, 0), *geo.pads, (0, 0)))
    s = geo.stride
    if s == 1:
        return xp[:, None]
    n, _, _, c = xp.shape
    hq, wq = geo.phase_hw
    x6 = xp.reshape(n, hq, s, wq, s, c)
    return jnp.stack([x6[:, :, a, :, b, :] for a, b in geo.phases], axis=1)


def conv_taps(geo: ConvGeometry, kernel) -> tuple:
    """((dy, dx), (phase, row, col)) for every tap of ``kernel``."""
    kh, kw = kernel
    return tuple(
        ((dy, dx), geo.tap(dy, dx)) for dy in range(kh) for dx in range(kw)
    )
