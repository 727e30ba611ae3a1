"""MobileNetV2, as published: Sandler et al., "MobileNetV2: Inverted
Residuals and Linear Bottlenecks", arXiv:1801.04381, Table 2 at width
multiplier 1.0 (Table 1's bottleneck: 1x1 expansion with ReLU6, 3x3
depthwise with ReLU6, linear 1x1 projection; a residual add where the
stride is 1 and the channels match).

Departures, each shared with the served program: batch norm is folded
into a per-channel bias (inference); padding is SAME, so a stride-2 layer
pads one more row and column at the bottom and right than at the top and
left.
"""

from __future__ import annotations

from bench.reference import ops

# Table 2: expansion t, output channels c, repeats n, first stride s
BOTTLENECKS = (
    (1, 16, 1, 1), (6, 24, 2, 2), (6, 32, 3, 2), (6, 64, 4, 2),
    (6, 96, 3, 1), (6, 160, 3, 2), (6, 320, 1, 1),
)
STEM, LAST = 32, 1280


def _blocks():
    """(prefix, d_in, expanded, d_out, stride) of every bottleneck."""
    d, out, k = STEM, [], 0
    for t, c, n, s in BOTTLENECKS:
        for i in range(n):
            k += 1
            out.append((f"b{k}", d, d * t, c, s if i == 0 else 1))
            d = c
    return out


def layers(cfg) -> list:
    hw = ops.out_hw(tuple(cfg["input_hw"]), 2)
    out = [ops.Layer("conv1", "conv", 3, STEM, 3, 2, hw)]
    for name, d_in, exp, d_out, stride in _blocks():
        if exp != d_in:
            out.append(ops.Layer(f"{name}_expand", "pointwise", d_in, exp, 1, 1, hw))
        hw = ops.out_hw(hw, stride)
        out.append(ops.Layer(f"{name}_dw", "dwconv", exp, exp, 3, stride, hw))
        out.append(ops.Layer(f"{name}_project", "pointwise", exp, d_out, 1, 1, hw, 1.0))
    out.append(ops.Layer("conv_last", "pointwise", 320, LAST, 1, 1, hw))
    out.append(ops.Layer("fc", "dense", LAST, cfg["num_classes"], 1, 1, (1, 1), 1.0))
    return out


def forward(params, x, cfg, num=ops.HIGHEST):
    y = ops.relu6(ops.conv(x.astype(num.store), params["conv1"], 2, num))
    for name, d_in, exp, d_out, stride in _blocks():
        h = y
        if exp != d_in:
            h = ops.relu6(ops.dense(h, params[f"{name}_expand"], num))
        h = ops.relu6(ops.dwconv(h, params[f"{name}_dw"], stride, num))
        h = ops.dense(h, params[f"{name}_project"], num)
        y = h + y if stride == 1 and d_in == d_out else h
    y = ops.relu6(ops.dense(y, params["conv_last"], num))
    return ops.dense(ops.gap(y), params["fc"], num)
