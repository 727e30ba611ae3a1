"""Data-rate algebra for continuous-flow accelerators (paper §II).

Rates are exact ``fractions.Fraction`` values in **features per clock**
(the paper's r).  A rate ``r`` entering a layer with ``d_in`` channels
corresponds to a *pixel* rate ``q = r / d_in`` (pixels per clock).

Rate propagation through a layer in steady state:

    q_out = q_in * (H_out * W_out) / (H_in * W_in)      (spatial decimation)
    r_out = q_out * d_out                               (channel expansion)

Pooling and strided convolutions reduce ``q`` — exactly the effect the
paper's data-rate-aware design exploits: downstream layers need fewer
arithmetic units per output.
"""

from __future__ import annotations

import dataclasses
from fractions import Fraction
from typing import List, Sequence, Tuple

LayerKind = str
# 'conv' | 'dwconv' | 'pointwise' | 'dense' | 'pool' | 'add' | 'gap' | 'concat'
#   | 'scale' | 'split' | 'merge'
# A 'conv' may be grouped (``LayerSpec.groups``); depthwise stays 'dwconv'.
# 'add', 'concat' and 'scale' are JOIN kinds: in a LayerGraph they may have
# several producers (residual sums, inception-style concatenations,
# squeeze-and-excitation gates).  For 'add', d_in is the per-operand
# channel count; for 'concat' it is the sum over operands.  'scale'
# multiplies a trunk stream [H, W, C] per channel by a gate of one 1x1 pixel
# [C] per frame (its second producer): d_in == d_out == C and the gate's
# pixel rate is the trunk's divided by H*W.  'split' / 'merge' are the Multi-CLP replication wiring of
# core.replicate: a 'split' round-robin-deals its frame stream across its
# >= 2 consumers (each lane carries pixel rate q / R), and a 'merge'
# re-interleaves R lane streams in order (q_out = q_lane * R).  Both are
# wiring only — no arithmetic.  Chains (the original API) never contain
# joins, splits, or merges.


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    """Static description of one layer of the network graph (chain or DAG)."""

    name: str
    kind: LayerKind
    d_in: int
    d_out: int
    in_hw: Tuple[int, int]
    out_hw: Tuple[int, int]
    kernel: Tuple[int, int] = (1, 1)
    stride: Tuple[int, int] = (1, 1)
    channel_multiplier: int = 1       # depthwise only
    padding: str = "same"
    # post-layer nonlinearity ('none' | 'relu' | 'relu6' | 'swish' |
    # 'sigmoid'; swish is x * sigmoid(x)).  Irrelevant to
    # the rate/DSE algebra (activations are free on the FPGA datapath) but
    # carried on the spec so the executable JAX network (models/cnn.py) is
    # generated from the *same* description as the DSE graph — topology
    # and inference cannot drift.
    activation: str = "none"
    # grouped conv ('conv' only): input and output channels split into
    # ``groups`` equal groups, each output channel reading the d_in /
    # groups channels of its own group.  Left out of the repr, so a
    # dense layer prints as it always has.
    groups: int = dataclasses.field(default=1, repr=False)

    def __post_init__(self):
        if self.groups != 1 and (
            self.kind != "conv" or self.d_in % self.groups or self.d_out % self.groups
        ):
            raise ValueError(
                f"{self.name}: {self.groups} groups need a 'conv' whose d_in "
                f"and d_out they divide (kind {self.kind!r}, {self.d_in} -> "
                f"{self.d_out})"
            )

    @property
    def group_in(self) -> int:
        """Input channels each output channel reads: d_in / groups."""
        return self.d_in // self.groups

    @property
    def k_taps(self) -> int:
        return self.kernel[0] * self.kernel[1]

    @property
    def spatial_ratio(self) -> Fraction:
        """out_pixels / in_pixels — the pixel-rate decimation factor."""
        return Fraction(
            self.out_hw[0] * self.out_hw[1], self.in_hw[0] * self.in_hw[1]
        )

    @property
    def macs_per_pixel(self) -> int:
        """Multiply ops per *output* pixel (the workload, not the hardware)."""
        if self.kind == "conv":
            return self.group_in * self.d_out * self.k_taps
        if self.kind == "dwconv":
            return self.d_in * self.channel_multiplier * self.k_taps
        if self.kind in ("pointwise", "dense"):
            return self.d_in * self.d_out
        return 0  # pool / add / gap have no multiplies

    @property
    def total_macs(self) -> int:
        return self.macs_per_pixel * self.out_hw[0] * self.out_hw[1]

    @property
    def weight_count(self) -> int:
        if self.kind == "conv":
            return self.group_in * self.d_out * self.k_taps + self.d_out
        if self.kind == "dwconv":
            return self.d_in * self.channel_multiplier * self.k_taps + self.d_out
        if self.kind in ("pointwise", "dense"):
            return self.d_in * self.d_out + self.d_out
        return 0


@dataclasses.dataclass(frozen=True)
class RatePoint:
    """The data rate at one edge of the chain."""

    features_per_clock: Fraction   # the paper's r
    d: int                         # channels at this edge

    @property
    def pixels_per_clock(self) -> Fraction:
        return self.features_per_clock / self.d

    def __repr__(self) -> str:  # pragma: no cover - debug aid
        r = self.features_per_clock
        return f"RatePoint({r.numerator}/{r.denominator} feat/clk, d={self.d})"


def propagate(rate_in: RatePoint, layer: LayerSpec) -> RatePoint:
    """Steady-state output rate of ``layer`` given its input rate."""
    if layer.d_in != rate_in.d:
        raise ValueError(
            f"{layer.name}: d_in={layer.d_in} but incoming rate has d={rate_in.d}"
        )
    q_out = rate_in.pixels_per_clock * layer.spatial_ratio
    return RatePoint(features_per_clock=q_out * layer.d_out, d=layer.d_out)


def propagate_chain(
    input_rate: Fraction, layers: Sequence[LayerSpec]
) -> List[RatePoint]:
    """Rates at every edge: [input, after layer0, after layer1, ...]."""
    if not layers:
        return []
    pts = [RatePoint(features_per_clock=input_rate, d=layers[0].d_in)]
    for layer in layers:
        pts.append(propagate(pts[-1], layer))
    return pts


def divisors(n: int) -> List[int]:
    """All positive divisors of n, ascending."""
    if n <= 0:
        raise ValueError(f"divisors({n})")
    small, large = [], []
    i = 1
    while i * i <= n:
        if n % i == 0:
            small.append(i)
            if i != n // i:
                large.append(n // i)
        i += 1
    return small + large[::-1]


def frame_cycles(hw: Tuple[int, int], pixels_per_clock: Fraction) -> Fraction:
    """Clock cycles to stream one frame through the accelerator input.

    Matches the paper's Table II throughput model: one blank column per
    image row for sliding-window flushing, i.e. (W+1)*H pixel slots.
    (224x224 @ 403.71 MHz, 2 px/clk -> 16,020 FPS exactly as published.)
    """
    h, w = hw
    return Fraction((w + 1) * h) / pixels_per_clock


def fps(hw: Tuple[int, int], pixels_per_clock: Fraction, f_hz: float) -> float:
    """Frames per second at clock ``f_hz``."""
    return f_hz / float(frame_cycles(hw, pixels_per_clock))
