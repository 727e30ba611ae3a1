"""ResNet-18, as published: He et al., "Deep Residual Learning for Image
Recognition", arXiv:1512.03385, Table 1 (18-layer), with the basic block
of Fig. 2 and projection shortcuts (option B) where the shape changes.

Departures, each shared with the served program: batch norm is folded
into a per-channel bias (inference); padding is SAME, so a stride-2 layer
pads one more row and column at the bottom and right than at the top and
left.
"""

from __future__ import annotations

from bench.reference import ops

STAGES = ((64, 2), (128, 2), (256, 2), (512, 2))


def _block_names(cfg):
    """(prefix, d_in, d_out, stride, projection) of every basic block."""
    d, out = 64, []
    for si, (ch, blocks) in enumerate(STAGES, start=1):
        for bi in range(blocks):
            stride = 2 if si > 1 and bi == 0 else 1
            out.append((f"l{si}b{bi + 1}", d, ch, stride, stride != 1 or d != ch))
            d = ch
    return out


def layers(cfg) -> list:
    hw = ops.out_hw(tuple(cfg["input_hw"]), 2)
    out = [ops.Layer("conv1", "conv", 3, 64, 7, 2, hw)]
    hw = ops.out_hw(hw, 2)  # max pool
    for name, d_in, d_out, stride, proj in _block_names(cfg):
        hw = ops.out_hw(hw, stride)
        out.append(ops.Layer(f"{name}_conv1", "conv", d_in, d_out, 3, stride, hw))
        out.append(ops.Layer(f"{name}_conv2", "conv", d_out, d_out, 3, 1, hw, 1.0))
        if proj:
            out.append(ops.Layer(f"{name}_down", "conv", d_in, d_out, 1, stride, hw, 1.0))
    out.append(ops.Layer("fc", "dense", 512, cfg["num_classes"], 1, 1, (1, 1), 1.0))
    return out


def forward(params, x, cfg, num=ops.HIGHEST):
    y = ops.relu(ops.conv(x.astype(num.store), params["conv1"], 2, num))
    y = ops.maxpool(y, 3, 2)
    for name, _, _, stride, proj in _block_names(cfg):
        h = ops.relu(ops.conv(y, params[f"{name}_conv1"], stride, num))
        h = ops.conv(h, params[f"{name}_conv2"], 1, num)
        short = ops.conv(y, params[f"{name}_down"], stride, num) if proj else y
        y = ops.relu(h + short)
    return ops.dense(ops.gap(y), params["fc"], num)
