"""EfficientNet-B0 — MobileNetV2's inverted residual with a
squeeze-and-excitation gate in every block, swish, and 5x5 depthwise.

Tan & Le, "EfficientNet: Rethinking Model Scaling for Convolutional
Neural Networks", arXiv:1905.11946, Table 1 (the B0 baseline): a 3x3/2
stem to 32 channels, sixteen MBConv blocks, a 1x1 head to 1280, global
average pooling and the classifier.  As in the paper's §4 and the
authors' reference implementation, the gate reduces to 0.25 of the
block's *input* channels, and swish is x * sigmoid(x).

The graph comes from the same block walk as MobileNetV2's
(``mobilenet.inverted_residual_body``): the walk emits each block's gate
as gap -> dense (swish) -> dense (sigmoid) -> a 'scale' join of the
block's depthwise output (core.graph).  The executable network is the
shared ``LayerGraph`` executor (models/cnn.py); the 'scale' join runs as
the ``se_scale`` Pallas kernel on the rate-matched path.
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax.numpy as jnp

from repro.core.graph import LayerGraph
from repro.models import mobilenet
from repro.models.topology import dense_spec, gap_spec

B0 = mobilenet.InvertedResidualNet(
    rows=(
        # (expansion t, out channels c, repeats n, first stride s, kernel k)
        (1, 16, 1, 1, 3),
        (6, 24, 2, 2, 3),
        (6, 40, 2, 2, 5),
        (6, 80, 3, 2, 3),
        (6, 112, 3, 1, 5),
        (6, 192, 4, 2, 5),
        (6, 320, 1, 1, 3),
    ),
    act="swish",
    se_ratio=0.25,
)


def efficientnet_b0_graph(
    input_hw: Tuple[int, int] = (224, 224), num_classes: int = 1000
) -> LayerGraph:
    sink = mobilenet._GraphSink()
    d, hw = mobilenet.inverted_residual_body(sink, input_hw, B0, int, 1280)
    prev = sink.g.add(gap_spec("gap", d, hw), [sink.prev])
    sink.g.add(dense_spec("fc", d, num_classes), [prev])
    return sink.g


@dataclasses.dataclass(frozen=True)
class EfficientNetConfig:
    input_hw: Tuple[int, int] = (224, 224)
    num_classes: int = 1000
    dtype: jnp.dtype = jnp.float32

    def graph(self) -> LayerGraph:
        return efficientnet_b0_graph(self.input_hw, self.num_classes)
