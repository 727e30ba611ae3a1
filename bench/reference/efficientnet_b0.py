"""EfficientNet-B0, as published: Tan & Le, "EfficientNet: Rethinking
Model Scaling for Convolutional Neural Networks", arXiv:1905.11946,
Table 1 (the B0 baseline): a 3x3/2 stem to 32 channels, sixteen MBConv
blocks, a 1x1 head to 1280, global average pooling and the classifier.
An MBConv block is a 1x1 expansion by t (none where t = 1), a k x k
depthwise conv, a squeeze-and-excitation gate, and a linear 1x1
projection, with a residual add where the stride is 1 and the channels
match.  From the paper's §4 and the authors' reference implementation:
the gate reduces to 0.25 of the block's *input* channels (at least 1),
with swish on the reduction and a sigmoid on the expansion, and swish is
x * sigmoid(x) on every non-linear layer.

Departures, each shared with the served program: batch norm is folded
into a per-channel bias (inference); padding is SAME, so a stride-2 layer
pads one more row and column at the bottom and right than at the top and
left; drop-connect and dropout are absent, as at inference.
"""

from __future__ import annotations

import jax.numpy as jnp

from bench.reference import ops

# Table 1: expansion t, kernel k, first stride s, output channels c,
# repeats n
MBCONV = (
    (1, 3, 1, 16, 1), (6, 3, 2, 24, 2), (6, 5, 2, 40, 2), (6, 3, 2, 80, 3),
    (6, 5, 1, 112, 3), (6, 5, 2, 192, 4), (6, 3, 1, 320, 1),
)
STEM, LAST = 32, 1280
SE_RATIO = 0.25


def _blocks():
    """(prefix, d_in, expanded, squeezed, d_out, kernel, stride) of every
    MBConv block."""
    d, out, b = STEM, [], 0
    for t, k, s, c, n in MBCONV:
        for i in range(n):
            b += 1
            se = max(1, int(SE_RATIO * d))
            out.append((f"b{b}", d, d * t, se, c, k, s if i == 0 else 1))
            d = c
    return out


def layers(cfg) -> list:
    hw = ops.out_hw(tuple(cfg["input_hw"]), 2)
    out = [ops.Layer("conv1", "conv", 3, STEM, 3, 2, hw)]
    for name, d_in, exp, se, d_out, k, stride in _blocks():
        if exp != d_in:
            out.append(ops.Layer(f"{name}_expand", "pointwise", d_in, exp, 1, 1, hw))
        hw = ops.out_hw(hw, stride)
        out.append(ops.Layer(f"{name}_dw", "dwconv", exp, exp, k, stride, hw))
        out.append(ops.Layer(f"{name}_se_reduce", "dense", exp, se, 1, 1, (1, 1)))
        out.append(ops.Layer(f"{name}_se_expand", "dense", se, exp, 1, 1, (1, 1), 1.0))
        out.append(ops.Layer(f"{name}_project", "pointwise", exp, d_out, 1, 1, hw, 1.0))
    out.append(ops.Layer("conv_last", "pointwise", 320, LAST, 1, 1, hw))
    out.append(ops.Layer("fc", "dense", LAST, cfg["num_classes"], 1, 1, (1, 1), 1.0))
    return out


def sigmoid(x):
    return 1.0 / (1.0 + jnp.exp(-x))


def swish(x):
    return x * sigmoid(x)


def forward(params, x, cfg, num=ops.HIGHEST):
    y = swish(ops.conv(x.astype(num.store), params["conv1"], 2, num))
    for name, d_in, exp, _, d_out, _, stride in _blocks():
        h = y
        if exp != d_in:
            h = swish(ops.dense(h, params[f"{name}_expand"], num))
        h = swish(ops.dwconv(h, params[f"{name}_dw"], stride, num))
        s = swish(ops.dense(ops.gap(h), params[f"{name}_se_reduce"], num))
        gate = sigmoid(ops.dense(s, params[f"{name}_se_expand"], num))
        h = h * gate[:, None, None, :]
        h = ops.dense(h, params[f"{name}_project"], num)
        y = h + y if stride == 1 and d_in == d_out else h
    y = swish(ops.dense(y, params["conv_last"], num))
    return ops.dense(ops.gap(y), params["fc"], num)
