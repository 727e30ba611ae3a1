"""RegNetY — grouped-conv bottlenecks with a squeeze-and-excitation gate.

Radosavovic et al., "Designing Network Design Spaces", CVPR 2020,
arXiv:2003.13678, as configured in the pycls model zoo.  A RegNet's
widths come from a quantized linear rule (the paper's §3.3): block j of
``depth`` has the continuous width ``u_j = w0 + wa * j``, quantized to
``w0 * wm ** round(log(u_j / w0) / log(wm))`` rounded to a multiple of
8; consecutive blocks of one width form a stage.  Each width then
rounds to a whole number of ``group_w``-channel groups.  RegNetY-4.0GF
(depth 22, w0 96, wa 31.41, wm 2.24, group width 64, bottleneck ratio
1) has stages of 128 / 192 / 512 / 1088 channels, 2 / 6 / 12 / 2 blocks
deep, with 2 / 3 / 8 / 17 groups.

The Y block: a 1x1 conv to the block's width (ReLU); a 3x3 grouped conv
carrying the stage's stride in its first block (ReLU); the
squeeze-and-excitation gate of the inverted-residual walk
(``mobilenet._GraphSink.squeeze_excite``: gap -> dense to round(0.25 x
the block's input width), ReLU -> dense, sigmoid -> the 'scale' join);
a linear 1x1 conv; the residual 'add' with the block input, through a
strided 1x1 projection where the shape changes, then ReLU.  The stem is
a 3x3/2 conv to 32 channels, the head global average pooling and the
classifier.  The grouped 3x3 stays one grouped 'conv' node
(``LayerSpec.groups``) from the DSE down to the ``kpu_conv`` kernel's
grouped route.
"""
from __future__ import annotations

import dataclasses
import math
from typing import List, Tuple

import jax.numpy as jnp

from repro.core.graph import LayerGraph
from repro.models import mobilenet
from repro.models.topology import add_spec, conv_spec, dense_spec, gap_spec


def regnet_stages(
    depth: int, w0: int, wa: float, wm: float, group_w: int, q: int = 8
) -> List[Tuple[int, int, int]]:
    """(width, blocks, groups) of each stage by the paper's rule."""
    widths = []
    for j in range(depth):
        k = round(math.log((w0 + wa * j) / w0) / math.log(wm))
        widths.append(round(w0 * wm**k / q) * q)
    stages: List[Tuple[int, int, int]] = []
    for w in widths:
        g = min(group_w, w)
        w = max(g, round(w / g) * g)  # whole groups of g channels
        if stages and stages[-1][0] == w:
            stages[-1] = (w, stages[-1][1] + 1, w // g)
        else:
            stages.append((w, 1, w // g))
    return stages


@dataclasses.dataclass(frozen=True)
class RegNetConfig:
    """RegNetY-4.0GF by default (pycls ``RegNetY-4.0GF_dds_8gpu``)."""

    depth: int = 22
    w0: int = 96
    wa: float = 31.41
    wm: float = 2.24
    group_w: int = 64
    se_ratio: float = 0.25
    stem_w: int = 32
    input_hw: Tuple[int, int] = (224, 224)
    num_classes: int = 1000
    dtype: jnp.dtype = jnp.float32

    def stages(self) -> List[Tuple[int, int, int]]:
        return regnet_stages(self.depth, self.w0, self.wa, self.wm, self.group_w)

    def graph(self) -> LayerGraph:
        sink = mobilenet._GraphSink()
        spec, hw = conv_spec("stem", "conv", 3, self.stem_w, self.input_hw, 3, 2,
                             act="relu")
        sink.layer(spec)
        d = self.stem_w
        for si, (w, blocks, groups) in enumerate(self.stages(), start=1):
            for bi in range(blocks):
                name, stride = f"s{si}b{bi + 1}", 2 if bi == 0 else 1
                sink.start_block()
                in_hw = hw
                spec, _ = conv_spec(f"{name}_a", "pointwise", d, w, hw, 1, 1,
                                    act="relu")
                sink.layer(spec)
                spec, hw = conv_spec(f"{name}_b", "conv", w, w, hw, 3, stride,
                                     act="relu", groups=groups)
                sink.layer(spec)
                sink.squeeze_excite(name, w, round(self.se_ratio * d), hw, "relu")
                spec, _ = conv_spec(f"{name}_c", "pointwise", w, w, hw, 1, 1)
                sink.layer(spec)
                shortcut = sink.block_in
                if stride != 1 or d != w:
                    spec, _ = conv_spec(f"{name}_proj", "conv", d, w, in_hw, 1,
                                        stride)
                    shortcut = sink.g.add(spec, [shortcut])
                sink.prev = sink.g.add(add_spec(f"{name}_add", w, hw, act="relu"),
                                       [sink.prev, shortcut])
                d = w
        prev = sink.g.add(gap_spec("gap", d, hw), [sink.prev])
        sink.g.add(dense_spec("fc", d, self.num_classes), [prev])
        return sink.g
