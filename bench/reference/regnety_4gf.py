"""RegNetY-4.0GF, as published: Radosavovic et al., "Designing Network
Design Spaces", CVPR 2020, arXiv:2003.13678, as configured in the pycls
model zoo (depth 22, w0 96, wa 31.41, wm 2.24, group width 64,
bottleneck ratio 1, SE ratio 0.25): a 3x3/2 stem to 32 channels, four
stages of Y blocks, global average pooling and the classifier.  The
stages' widths, depths and groups are written out below, as the paper's
quantized linear rule gives them for those parameters.

A Y block is a 1x1 conv to the stage's width with ReLU; a 3x3 conv in
groups of 64 channels, carrying the stage's stride 2 in its first block,
with ReLU; a squeeze-and-excitation gate (global average pool, dense to
round(0.25 x the block's input width) with ReLU, dense back with a
sigmoid, a channel-wise scale); a linear 1x1 conv; and the residual add
with the block's input, through a strided 1x1 projection where the
shape changes, followed by ReLU.

Departures, each shared with the served program: batch norm is folded
into a per-channel bias (inference); padding is SAME, so a stride-2 layer
pads one more row and column at the bottom and right than at the top and
left.
"""

from __future__ import annotations

import jax.numpy as jnp

from bench.reference import ops

# (width, blocks) of each stage; every stage's first block has stride 2
STAGES = ((128, 2), (192, 6), (512, 12), (1088, 2))
GROUP_W, STEM, SE_RATIO = 64, 32, 0.25


def _blocks():
    """(prefix, d_in, width, squeezed, stride, projection) of every block."""
    d, out = STEM, []
    for si, (w, n) in enumerate(STAGES, start=1):
        for bi in range(n):
            s = 2 if bi == 0 else 1
            out.append((f"s{si}b{bi + 1}", d, w, round(SE_RATIO * d), s,
                        s != 1 or d != w))
            d = w
    return out


def layers(cfg) -> list:
    """A grouped conv is listed with the channels each output reads
    (``GROUP_W``) as its ``d_in``: its weight, fan-in and multiply-adds
    are then the grouped ones."""
    hw = ops.out_hw(tuple(cfg["input_hw"]), 2)
    out = [ops.Layer("stem", "conv", 3, STEM, 3, 2, hw)]
    for name, d_in, w, se, stride, proj in _blocks():
        out.append(ops.Layer(f"{name}_a", "pointwise", d_in, w, 1, 1, hw))
        hw = ops.out_hw(hw, stride)
        out.append(ops.Layer(f"{name}_b", "conv", GROUP_W, w, 3, stride, hw))
        out.append(ops.Layer(f"{name}_se_reduce", "dense", w, se, 1, 1, (1, 1)))
        out.append(ops.Layer(f"{name}_se_expand", "dense", se, w, 1, 1, (1, 1), 1.0))
        out.append(ops.Layer(f"{name}_c", "pointwise", w, w, 1, 1, hw, 1.0))
        if proj:
            out.append(ops.Layer(f"{name}_proj", "conv", d_in, w, 1, stride, hw, 1.0))
    out.append(ops.Layer("fc", "dense", STAGES[-1][0], cfg["num_classes"],
                         1, 1, (1, 1), 1.0))
    return out


def sigmoid(x):
    return 1.0 / (1.0 + jnp.exp(-x))


def grouped_conv(x, p, stride, num):
    groups = x.shape[-1] // p["w"].shape[2]
    return ops._conv(x, p["w"], stride, groups, num) + p["b"].astype(num.store)


def forward(params, x, cfg, num=ops.HIGHEST):
    y = ops.relu(ops.conv(x.astype(num.store), params["stem"], 2, num))
    for name, _, _, _, stride, proj in _blocks():
        h = ops.relu(ops.dense(y, params[f"{name}_a"], num))
        h = ops.relu(grouped_conv(h, params[f"{name}_b"], stride, num))
        s = ops.relu(ops.dense(ops.gap(h), params[f"{name}_se_reduce"], num))
        gate = sigmoid(ops.dense(s, params[f"{name}_se_expand"], num))
        h = ops.dense(h * gate[:, None, None, :], params[f"{name}_c"], num)
        short = ops.conv(y, params[f"{name}_proj"], stride, num) if proj else y
        y = ops.relu(h + short)
    return ops.dense(ops.gap(y), params["fc"], num)
