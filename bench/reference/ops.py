"""Layer arithmetic shared by the plain references: NHWC activations,
HWIO weights, SAME padding, folded batch norm (a bias per channel).

Every op takes ``Numerics``: the dtype activations and weights are kept
in, the dtype the operands of a conv or matmul are rounded to, and the
matmul precision.  ``HIGHEST`` is float32 throughout; ``HIGH`` is the
TPU's three-pass float32 (``Precision.HIGH``), written out so that it
reads the same on any backend: each operand split into a bfloat16 high
part and a bfloat16 low part, and the three products other than low x
low summed in float32; ``ONE_PASS`` keeps float32 but rounds each conv
and matmul operand to bfloat16 and accumulates in float32, as one pass of
the TPU's matrix unit does.  Depthwise convs (the vector unit's work)
stay float32 in both.  ``BFLOAT16`` keeps everything in bfloat16.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
from jax import lax


@dataclasses.dataclass(frozen=True)
class Numerics:
    store: object
    operand: object
    precision: object
    depthwise: object = None  # the Numerics of depthwise convs, if others
    three_pass: bool = False  # operands split into bfloat16 high + low parts


HIGHEST = Numerics(jnp.float32, jnp.float32, lax.Precision.HIGHEST)
HIGH = Numerics(jnp.float32, jnp.bfloat16, lax.Precision.DEFAULT, HIGHEST, True)
ONE_PASS = Numerics(jnp.float32, jnp.bfloat16, lax.Precision.DEFAULT, HIGHEST)
BFLOAT16 = Numerics(jnp.bfloat16, jnp.bfloat16, lax.Precision.DEFAULT)
NUMERICS = {"highest": HIGHEST, "high": HIGH, "one_pass": ONE_PASS,
            "bfloat16": BFLOAT16}


@dataclasses.dataclass(frozen=True)
class Layer:
    """One weighted layer: ``kind`` is conv, dwconv, pointwise or dense;
    ``hw`` is its output's spatial size (1x1 for dense)."""

    name: str
    kind: str
    d_in: int
    d_out: int
    k: int
    stride: int
    hw: tuple
    gain: float = 2.0  # 2 when a ReLU follows, 1 when nothing does

    @property
    def w_shape(self) -> tuple:
        if self.kind == "conv":
            return (self.k, self.k, self.d_in, self.d_out)
        if self.kind == "dwconv":
            return (self.k, self.k, 1, self.d_out)
        return (self.d_in, self.d_out)

    @property
    def fan_in(self) -> int:
        if self.kind == "conv":
            return self.k * self.k * self.d_in
        if self.kind == "dwconv":
            return self.k * self.k
        return self.d_in

    @property
    def macs(self) -> int:
        """Multiply-accumulates for one frame."""
        taps = self.k * self.k if self.kind in ("conv", "dwconv") else 1
        per_px = taps * (1 if self.kind == "dwconv" else self.d_in) * self.d_out
        return per_px * self.hw[0] * self.hw[1]


def out_hw(hw: tuple, stride: int) -> tuple:
    """SAME padding: ceil(size / stride)."""
    return (-(-hw[0] // stride), -(-hw[1] // stride))


def init(layers, key) -> dict:
    """He-normal weights (gain 2 before a ReLU, 1 before none: a linear
    projection, a residual branch's last conv, the classifier) and
    N(0, 0.1^2) biases, the folded batch norm's shift; one key per layer,
    float32."""
    params = {}
    for i, layer in enumerate(layers):
        kw, kb = jax.random.split(jax.random.fold_in(key, i))
        w = jax.random.normal(kw, layer.w_shape, jnp.float32)
        params[layer.name] = {
            "w": w * math.sqrt(layer.gain / layer.fan_in),
            "b": 0.1 * jax.random.normal(kb, (layer.d_out,), jnp.float32),
        }
    return params


def macs(layers) -> int:
    return sum(layer.macs for layer in layers)


def _split(x):
    """float32 ``x`` as bfloat16 high and low parts.  The high part is
    rounded to nearest even on the bits: XLA may keep a float32 ->
    bfloat16 -> float32 round trip in float32 (excess precision), which
    would leave the low part nought."""
    bits = lax.bitcast_convert_type(x, jnp.uint32)
    bits = (bits + jnp.uint32(0x7FFF) + ((bits >> 16) & 1)) & jnp.uint32(0xFFFF0000)
    hi = lax.bitcast_convert_type(bits, jnp.float32)
    return hi.astype(jnp.bfloat16), (x - hi).astype(jnp.bfloat16)


def _products(f, x, w, num):
    """``f(x, w)`` with the operands at ``num``'s precision."""
    if not num.three_pass:
        return f(x.astype(num.operand), w.astype(num.operand))
    (xh, xl), (wh, wl) = _split(x), _split(w)
    return (f(xh, wl) + f(xl, wh)) + f(xh, wh)


def _conv(x, w, stride, groups, num):
    return _products(lambda a, b: lax.conv_general_dilated(
        a, b, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=groups,
        precision=num.precision, preferred_element_type=num.store,
    ), x, w, num)


def conv(x, p, stride, num):
    return _conv(x, p["w"], stride, 1, num) + p["b"].astype(num.store)


def dwconv(x, p, stride, num):
    num = num.depthwise or num
    return _conv(x, p["w"], stride, x.shape[-1], num) + p["b"].astype(num.store)


def dense(x, p, num):
    """A 1x1 conv on [N, H, W, C], or the classifier on [N, C]."""
    y = _products(lambda a, b: jnp.tensordot(
        a, b, axes=1, precision=num.precision, preferred_element_type=num.store,
    ), x, p["w"], num)
    return y + p["b"].astype(num.store)


def maxpool(x, k, stride):
    return lax.reduce_window(
        x, jnp.array(-jnp.inf, x.dtype), lax.max,
        (1, k, k, 1), (1, stride, stride, 1), "SAME",
    )


def gap(x):
    return jnp.mean(x, axis=(1, 2))


def relu(x):
    return jnp.maximum(x, 0)


def relu6(x):
    return jnp.clip(x, 0, 6)
