"""MobileNetV1/V2 — the paper's evaluation models.

Two faces, generated from one block description:

1. ``mobilenet_v1_chain()`` / ``mobilenet_v2_chain()`` — the ``LayerSpec``
   chains consumed by the core DSE + resource model (Tables I & II), and
   ``mobilenet_v2_graph()`` — the true DAG with residual joins.
2. JAX inference (NHWC, folded BN, optional int8 simulated
   quantization to honour the paper's 8-bit datapath) through
   ``registry.get_cnn_api("mobilenet_v2")``: the shared ``LayerGraph``
   executor in models/cnn.py.  A ``conv_impls`` mapping lets the caller
   swap XLA convs for the Pallas KPU/FCU/DW kernels
   (repro.kernels.*.ops).

The executor interprets the same graph the DSE plans, asserting per-node
shapes/MACs against the specs, so topology and inference cannot drift.
BatchNorm is folded into conv scale/bias (inference-time, as on the FPGA).
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Tuple

import jax.numpy as jnp

from repro.core.graph import LayerGraph
from repro.core.rate import LayerSpec
from repro.models.topology import (
    add_spec,
    conv_spec as _conv,
    dense_spec,
    gap_spec,
    scale_spec,
)


# ==========================================================================
# LayerSpec chains (the DSE's view)
# ==========================================================================


def mobilenet_v1_chain(
    input_hw: Tuple[int, int] = (224, 224),
    alpha: float = 1.0,
    num_classes: int = 1000,
) -> List[LayerSpec]:
    def c(ch):
        return max(8, int(ch * alpha))

    layers: List[LayerSpec] = []
    hw = input_hw
    spec, hw = _conv("conv1", "conv", 3, c(32), hw, 3, 2, act="relu6")
    layers.append(spec)
    # (dw stride, pw out channels)
    cfg = [
        (1, 64),
        (2, 128),
        (1, 128),
        (2, 256),
        (1, 256),
        (2, 512),
        (1, 512),
        (1, 512),
        (1, 512),
        (1, 512),
        (1, 512),
        (2, 1024),
        (1, 1024),
    ]
    d = c(32)
    for i, (s, out) in enumerate(cfg):
        spec, hw = _conv(f"dw{i + 1}", "dwconv", d, d, hw, 3, s, act="relu6")
        layers.append(spec)
        spec, hw = _conv(f"pw{i + 1}", "pointwise", d, c(out), hw, 1, 1, act="relu6")
        layers.append(spec)
        d = c(out)
    layers.append(gap_spec("gap", d, hw))
    layers.append(dense_spec("fc", d, num_classes))
    return layers


@dataclasses.dataclass(frozen=True)
class InvertedResidualNet:
    """An inverted-residual network's block description: a stem conv
    3x3/2 to 32 channels, stages of MBConv blocks, a 1x1 head.  One row per stage:
    (expansion t, out channels c, repeats n, first stride s, depthwise
    kernel k).  ``se_ratio`` > 0 adds a squeeze-and-excitation gate to
    every block, reducing to ``max(1, int(se_ratio * block input
    channels))``; ``act`` is every non-linear layer's activation."""

    rows: Tuple[Tuple[int, int, int, int, int], ...]
    act: str
    se_ratio: float = 0.0


MOBILENET_V2 = InvertedResidualNet(
    rows=(
        (1, 16, 1, 1, 3),
        (6, 24, 2, 2, 3),
        (6, 32, 3, 2, 3),
        (6, 64, 4, 2, 3),
        (6, 96, 3, 1, 3),
        (6, 160, 3, 2, 3),
        (6, 320, 1, 1, 3),
    ),
    act="relu6",
)


def _v2_channels(alpha: float):
    def c(ch):
        ch = int(ch * alpha)
        return max(8, (ch + 4) // 8 * 8)

    return c


class _ChainSink:
    """Collects the linear LayerSpec sequence; residual edges are dropped
    (the chain view the paper's Tables I/II are computed on)."""

    def __init__(self) -> None:
        self.layers: List[LayerSpec] = []

    def start_block(self) -> None:
        pass

    def layer(self, spec: LayerSpec) -> None:
        self.layers.append(spec)

    def join(self, name: str, d: int, hw: Tuple[int, int]) -> None:
        pass

    def squeeze_excite(self, name, d, d_se, hw, act) -> None:
        pass


class _GraphSink:
    """Builds the true DAG: an explicit 'add' join per residual block, and
    a 'scale' join per squeeze-and-excitation gate."""

    def __init__(self) -> None:
        self.g = LayerGraph()
        self.prev: Optional[str] = None
        self.block_in: Optional[str] = None

    def start_block(self) -> None:
        self.block_in = self.prev

    def layer(self, spec: LayerSpec) -> None:
        self.prev = self.g.add(spec, [self.prev] if self.prev is not None else [])

    def join(self, name: str, d: int, hw: Tuple[int, int]) -> None:
        self.prev = self.g.add(add_spec(name, d, hw), [self.prev, self.block_in])

    def squeeze_excite(self, name, d, d_se, hw, act) -> None:
        """gap -> dense reduce -> dense expand (sigmoid) -> the 'scale'
        join of the trunk by its frame's gate."""
        trunk = self.prev
        gap = self.g.add(gap_spec(f"{name}_se_gap", d, hw), [trunk])
        red = self.g.add(
            dense_spec(f"{name}_se_reduce", d, d_se, act=act), [gap])
        gate = self.g.add(
            dense_spec(f"{name}_se_expand", d_se, d, act="sigmoid"), [red])
        self.prev = self.g.add(scale_spec(f"{name}_scale", d, hw), [trunk, gate])


def inverted_residual_body(sink, input_hw, net: InvertedResidualNet, c, head):
    """Walk ``net``'s block description once, emitting into ``sink`` —
    the single source both the DSE topology and the executable net
    derive from.  ``c`` rounds a published channel count (the width
    multiplier); ``head`` is the 1x1 head's width.  Returns (final
    channels, final hw)."""
    act = net.act
    hw = input_hw
    spec, hw = _conv("conv1", "conv", 3, c(32), hw, 3, 2, act=act)
    sink.layer(spec)
    d = c(32)
    blk = 0
    for t, ch, n, s, k in net.rows:
        for i in range(n):
            blk += 1
            stride = s if i == 0 else 1
            exp = d * t
            sink.start_block()
            if t != 1:
                spec, hw = _conv(
                    f"b{blk}_expand", "pointwise", d, exp, hw, 1, 1, act=act
                )
                sink.layer(spec)
            spec, hw = _conv(
                f"b{blk}_dw", "dwconv", exp, exp, hw, k, stride, act=act
            )
            sink.layer(spec)
            if net.se_ratio:
                d_se = max(1, int(net.se_ratio * d))
                sink.squeeze_excite(f"b{blk}", exp, d_se, hw, act)
            # linear bottleneck: no activation on the projection
            spec, hw = _conv(
                f"b{blk}_project", "pointwise", exp, c(ch), hw, 1, 1, act="none"
            )
            sink.layer(spec)
            if stride == 1 and d == c(ch):
                sink.join(f"b{blk}_add", c(ch), hw)
            d = c(ch)
    spec, hw = _conv("conv_last", "pointwise", d, head, hw, 1, 1, act=act)
    sink.layer(spec)
    return head, hw


def _v2_body(sink, input_hw, alpha):
    c = _v2_channels(alpha)
    head = c(1280) if alpha > 1.0 else 1280
    return inverted_residual_body(sink, input_hw, MOBILENET_V2, c, head)


def mobilenet_v2_chain(
    input_hw: Tuple[int, int] = (224, 224),
    alpha: float = 1.0,
    num_classes: int = 1000,
) -> List[LayerSpec]:
    sink = _ChainSink()
    d, hw = _v2_body(sink, input_hw, alpha)
    sink.layers.append(gap_spec("gap", d, hw))
    sink.layers.append(dense_spec("fc", d, num_classes))
    return sink.layers


def mobilenet_v2_graph(
    input_hw: Tuple[int, int] = (224, 224),
    alpha: float = 1.0,
    num_classes: int = 1000,
) -> LayerGraph:
    """MobileNetV2 as a true DAG: inverted-residual blocks with stride 1
    and matching channels get an explicit 'add' join between the project
    output and the block input — the topology the FPGA dataflow actually
    builds (the chain variant drops the residual edges, underestimating
    both the skew FIFOs and the adders)."""
    sink = _GraphSink()
    d, hw = _v2_body(sink, input_hw, alpha)
    prev = sink.g.add(gap_spec("gap", d, hw), [sink.prev])
    sink.g.add(dense_spec("fc", d, num_classes), [prev])
    return sink.g


# ==========================================================================
# JAX model (NHWC, folded BN) — the shared executor on the same graph
# ==========================================================================


@dataclasses.dataclass(frozen=True)
class MobileNetConfig:
    version: int = 2
    input_hw: Tuple[int, int] = (224, 224)
    alpha: float = 1.0
    num_classes: int = 1000
    dtype: jnp.dtype = jnp.float32

    def chain(self) -> List[LayerSpec]:
        fn = mobilenet_v1_chain if self.version == 1 else mobilenet_v2_chain
        return fn(self.input_hw, self.alpha, self.num_classes)

    def graph(self) -> LayerGraph:
        """DAG view: v2 gets real residual joins; v1 is a linear graph."""
        if self.version == 2:
            return mobilenet_v2_graph(self.input_hw, self.alpha, self.num_classes)
        return LayerGraph.from_chain(self.chain())
