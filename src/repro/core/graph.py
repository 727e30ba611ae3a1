"""DAG rate graph — branch/join rate propagation, skew sizing, DAG DSE.

The paper's rate calculus (Eqs. 1-11) is formulated over a linear chain,
but its own evaluation model (MobileNetV2) has residual branches, and
every modern CNN worth serving is a DAG.  This module lifts the whole
pipeline — rate propagation, (j, h) selection, continuous-flow checking —
onto an explicit producer/consumer graph and adds the one genuinely new
piece of physics a DAG brings: **join skew buffers**.

In a dataflow FPGA design, when a stream forks (a residual branch) and
re-converges (the elementwise add), the trunk path is many pipeline
stages deep while the shortcut is a wire.  Pixel *n* of the shortcut
arrives long before pixel *n* of the trunk; a FIFO must park the early
pixels or the whole upstream pipeline backpressures and the continuous-
flow guarantee dies.  Sizing those FIFOs analytically (instead of "make
it deep and hope") is where BRAM is won or lost on branchy topologies
(Petrica et al., "Memory-Efficient Dataflow Inference for Deep CNNs on
FPGA").

Timing model (exact fractions, validated by ``schedule.simulate_graph``):

  A node's steady-state output stream is affine:  t_out(m) = offset +
  (m+1)/q_out.  One pass over a pixel takes C cycles (C = h*d_in/j for
  arithmetic layers, the pass cadence for pool/add/gap/concat), and a
  sliding window must bank half a kernel of rows before its first valid
  output, so

      offset(v) = max_{u in preds(v)} offset(u) + C(v) + fill(v),
      fill(v)   = ((k_h-1)//2 * W_in + (k_w-1)//2) / q_in(v).

  At a join, pixel n is consumable at the *latest* branch's arrival.
  The FIFO on an in-edge from u therefore holds at most

      floor(skew * q) + P    pixels,   skew = max_offset - offset(u)

  (P = the join's pixel phases; P extra slots cover multi-pixel intake).
  ``simulate_graph`` asserts the measured occupancy never exceeds this.

  A 'scale' join (squeeze-and-excitation) multiplies a trunk stream of
  H*W pixels a frame by a gate of one pixel a frame, computed from the
  whole frame (gap -> dense -> dense).  Pixel m of frame f needs gate f,
  which leaves its producer at offset_g + (f+1)*H*W/q, so in trunk-pixel
  terms the gate path's offset is offset_g + (H*W - 1)/q.  The trunk
  edge's FIFO therefore parks one whole frame plus the gate path's
  latency, floor(skew * q) + P pixels as above; the gate edge holds at
  most floor(skew * q / (H*W)) + 2 gates (the one in use and the next
  frame's).

Plan-threading contract (who produces what, who consumes it):

  ``plan_graph`` is the single producer of per-node kernel plans: its
  ``GraphPlan.kernel_plan()`` lowers every node's chosen ``LayerImpl``
  — the (j, h), phases, and decimation-adjusted demand the DAG DSE
  settled on — into an ``ImplPlan`` carrying a concrete Pallas tile
  (``core.tpu_tiles.select_tile_for_impl``).  The sole consumer is the
  graph executor ``models/cnn.py``: ``apply_graph(plan=...)`` dispatches
  each arithmetic node's kernel with its own tile instead of one global
  rate, and asserts at trace time that the tile the kernel *executed*
  equals the tile planned here.  Invariants: plan keys == graph node
  names; every node with a kernel (kind in ``core.tpu_tiles.
  KERNEL_KINDS``: the arithmetic kinds, and the 'scale' join) carries a
  tile whose dimensions divide the node's (d_in, d_out); for feasible
  impls the tile preserves Eq. 9 (capacity >= demand) under the
  MXU-alignment growth.
"""
from __future__ import annotations

import dataclasses
import math
from collections import OrderedDict
from fractions import Fraction
from typing import Dict, List, Optional, Sequence, Tuple

from .dse import LayerImpl, select_impl
from .hw_specs import TPUSpec, target_spec
from .rate import LayerSpec, RatePoint
from .stage_partition import (
    DEFAULT_LINK_CYCLES,
    EdgeTraffic,
    GraphStagePlan,
    LinkDtype,
    StreamBuffer,
    partition_graph,
    plan_node_costs,
    round_robin_placement,
    stage_stream_bits,
    stream_buffers,
)
from .tpu_tiles import KERNEL_KINDS, VMEM_FRACTION, TileChoice, select_tile_for_impl

JOIN_KINDS = ("add", "concat", "scale")
# Gates a 'scale' join holds beyond its skew: the current frame's, and the
# next frame's, which may arrive before the current frame's last pixel.
SCALE_GATE_SLOTS = 2


class GraphError(ValueError):
    """Structural or rate inconsistency in a LayerGraph."""


# ==========================================================================
# Graph structure
# ==========================================================================


class LayerGraph:
    """A DAG of ``LayerSpec`` nodes with producer→consumer edges.

    Nodes are added in topological order by construction (``add`` requires
    every producer to exist already), so ``topo_order()`` is simply the
    insertion order.  Branch points are nodes with more than one consumer
    (the stream is forked — each consumer sees the full rate); join nodes
    are 'add'/'concat'/'scale' specs with more than one producer.
    """

    def __init__(self) -> None:
        self._specs: "OrderedDict[str, LayerSpec]" = OrderedDict()
        self._preds: Dict[str, List[str]] = {}
        self._succs: Dict[str, List[str]] = {}

    # -- construction ------------------------------------------------------

    def add(self, spec: LayerSpec, inputs: Sequence[str] = ()) -> str:
        name = spec.name
        if name in self._specs:
            raise GraphError(f"duplicate node {name!r}")
        preds = list(inputs)
        for p in preds:
            if p not in self._specs:
                raise GraphError(f"{name}: unknown producer {p!r}")
        self._check_shapes(spec, preds)
        self._specs[name] = spec
        self._preds[name] = preds
        self._succs[name] = []
        for p in preds:
            self._succs[p].append(name)
        return name

    def _check_shapes(self, spec: LayerSpec, preds: List[str]) -> None:
        if spec.kind == "scale":
            self._check_scale(spec, preds)
        elif spec.kind in JOIN_KINDS:
            if len(preds) < 2:
                raise GraphError(
                    f"{spec.name}: join kind {spec.kind!r} "
                    f"needs >=2 producers, got {len(preds)}"
                )
            for p in preds:
                if self._specs[p].out_hw != spec.in_hw:
                    raise GraphError(
                        f"{spec.name}: producer {p} emits {self._specs[p].out_hw}"
                        f" but join expects {spec.in_hw}"
                    )
            d_ops = [self._specs[p].d_out for p in preds]
            if spec.kind == "add":
                if any(d != spec.d_in for d in d_ops) or spec.d_out != spec.d_in:
                    raise GraphError(
                        f"{spec.name}: add needs equal operand channels "
                        f"(=d_in=d_out), got operands {d_ops}, "
                        f"d_in={spec.d_in}, d_out={spec.d_out}"
                    )
            else:  # concat
                if sum(d_ops) != spec.d_in or spec.d_out != spec.d_in:
                    raise GraphError(
                        f"{spec.name}: concat d_in must equal sum of operand "
                        f"channels {sum(d_ops)}, got d_in={spec.d_in}, "
                        f"d_out={spec.d_out}"
                    )
        elif spec.kind == "merge":
            # Multi-CLP lane re-interleave (core.replicate): >= 2 equal-shape
            # lane streams, d_in == d_out == each operand's channel count.
            if len(preds) < 2:
                raise GraphError(
                    f"{spec.name}: merge needs >=2 lane producers, "
                    f"got {len(preds)}"
                )
            for p in preds:
                if self._specs[p].out_hw != spec.in_hw:
                    raise GraphError(
                        f"{spec.name}: lane {p} emits {self._specs[p].out_hw}"
                        f" but merge expects {spec.in_hw}"
                    )
                if self._specs[p].d_out != spec.d_in:
                    raise GraphError(
                        f"{spec.name}: lane {p} has "
                        f"d_out={self._specs[p].d_out}, merge d_in={spec.d_in}"
                    )
            if spec.d_out != spec.d_in or spec.out_hw != spec.in_hw:
                raise GraphError(
                    f"{spec.name}: merge is wiring only — needs "
                    f"d_out == d_in and out_hw == in_hw"
                )
        else:
            if len(preds) > 1:
                raise GraphError(
                    f"{spec.name}: kind {spec.kind!r} takes at "
                    f"most one producer, got {len(preds)}"
                )
            if spec.kind == "split" and (
                spec.d_out != spec.d_in or spec.out_hw != spec.in_hw
            ):
                raise GraphError(
                    f"{spec.name}: split is wiring only — needs "
                    f"d_out == d_in and out_hw == in_hw"
                )
            if preds:
                pred = self._specs[preds[0]]
                if pred.d_out != spec.d_in:
                    raise GraphError(
                        f"{spec.name}: d_in={spec.d_in} but "
                        f"producer {pred.name} has d_out={pred.d_out}"
                    )
                if pred.out_hw != spec.in_hw:
                    raise GraphError(
                        f"{spec.name}: in_hw={spec.in_hw} but "
                        f"producer {pred.name} emits {pred.out_hw}"
                    )

    def _check_scale(self, spec: LayerSpec, preds: List[str]) -> None:
        """A 'scale' join takes [trunk, gate]: the trunk at the join's
        H x W, the gate one 1x1 pixel a frame, both with the join's C
        channels."""
        if len(preds) != 2:
            raise GraphError(
                f"{spec.name}: scale needs [trunk, gate] producers, "
                f"got {len(preds)}"
            )
        if spec.d_out != spec.d_in or spec.out_hw != spec.in_hw:
            raise GraphError(
                f"{spec.name}: scale keeps its trunk's shape — needs "
                f"d_out == d_in and out_hw == in_hw"
            )
        trunk, gate = (self._specs[p] for p in preds)
        if trunk.out_hw != spec.in_hw:
            raise GraphError(
                f"{spec.name}: trunk {trunk.name} emits {trunk.out_hw} "
                f"but scale expects {spec.in_hw}"
            )
        if gate.out_hw != (1, 1):
            raise GraphError(
                f"{spec.name}: gate {gate.name} emits {gate.out_hw}, "
                f"not one 1x1 pixel a frame"
            )
        for p in (trunk, gate):
            if p.d_out != spec.d_in:
                raise GraphError(
                    f"{spec.name}: scale needs equal operand channels "
                    f"(=d_in), got {p.name} d_out={p.d_out}, d_in={spec.d_in}"
                )

    @classmethod
    def from_chain(cls, layers: Sequence[LayerSpec]) -> "LayerGraph":
        g = cls()
        prev: Optional[str] = None
        for spec in layers:
            prev = g.add(spec, [prev] if prev is not None else [])
        return g

    # -- accessors ---------------------------------------------------------

    def spec(self, name: str) -> LayerSpec:
        return self._specs[name]

    def preds(self, name: str) -> List[str]:
        return list(self._preds[name])

    def succs(self, name: str) -> List[str]:
        return list(self._succs[name])

    def topo_order(self) -> List[str]:
        return list(self._specs)

    def __len__(self) -> int:
        return len(self._specs)

    def __contains__(self, name: str) -> bool:
        return name in self._specs

    @property
    def input_nodes(self) -> List[str]:
        return [n for n in self._specs if not self._preds[n]]

    @property
    def output_nodes(self) -> List[str]:
        return [n for n in self._specs if not self._succs[n]]

    def joins(self) -> List[str]:
        return [n for n in self._specs if len(self._preds[n]) > 1]

    def branches(self) -> List[str]:
        return [n for n in self._specs if len(self._succs[n]) > 1]

    def is_linear(self) -> bool:
        return all(
            len(self._preds[n]) <= 1 and len(self._succs[n]) <= 1
            for n in self._specs
        )

    def to_chain(self) -> List[LayerSpec]:
        if not self.is_linear() or len(self.input_nodes) != 1:
            raise GraphError("graph is not a single linear chain")
        return [self._specs[n] for n in self.topo_order()]


# ==========================================================================
# Rate propagation (the DAG lift of rate.propagate_chain)
# ==========================================================================


def propagate_graph(
    graph: LayerGraph, input_rate: Fraction
) -> Tuple[Dict[str, Fraction], Dict[str, RatePoint]]:
    """Exact steady-state rates over the DAG.

    Returns ``(demands, out_points)``: the features/clock each node must
    absorb (the DSE's r; for 'add' this is the per-operand rate — every
    operand stream runs at the same q by the join-consistency check) and
    the RatePoint each node emits.

    Every source node receives ``input_rate``.  Joins require all operand
    *pixel* rates to agree — a structural property of correct CNN DAGs
    (both residual paths decimate identically); violations raise.  A
    'scale' join runs at its trunk's rate, and its gate must arrive at
    that rate over the trunk's H*W pixels: one gate a frame.

    Replication wiring (core.replicate) extends the fluid algebra:

    * a 'split' node round-robin-deals its stream over its R >= 2
      consumers, so it *emits* the per-lane pixel rate q_in / R (each
      lane carries 1/R of the frames — Eq. 9 feasibility on a lane is
      checked against rate/R);
    * a 'merge' node re-interleaves its R lane streams, so its demand is
      the full restored rate q_lane * d_in * R (the adder-free datapath
      must keep up with the *combined* stream) and it emits
      q_out = q_lane * R — exactly the q the unreplicated node emitted,
      which is how Eq. 9/10 continuous flow is preserved downstream.
    """
    demands: Dict[str, Fraction] = {}
    out: Dict[str, RatePoint] = {}
    for name in graph.topo_order():
        spec = graph.spec(name)
        preds = graph.preds(name)
        if not preds:
            q_in = Fraction(input_rate) / spec.d_in
        elif spec.kind == "scale":
            q_in = out[preds[0]].pixels_per_clock
            q_gate = out[preds[1]].pixels_per_clock
            if q_gate * frame_pixels(spec) != q_in:
                raise GraphError(
                    f"{name}: gate {preds[1]} runs at {q_gate} pixels/clock, "
                    f"not one per frame of trunk {preds[0]} ({q_in} over "
                    f"{frame_pixels(spec)} pixels)"
                )
        else:
            qs = {out[p].pixels_per_clock for p in preds}
            if len(qs) > 1:
                raise GraphError(
                    f"{name}: operand pixel rates disagree: "
                    + ", ".join(f"{p}={out[p].pixels_per_clock}" for p in preds)
                )
            q_in = qs.pop()
        if spec.kind == "split":
            fanout = len(graph.succs(name))
            if fanout < 2:
                raise GraphError(
                    f"{name}: split needs >=2 lane consumers, got {fanout}"
                )
            demands[name] = q_in * spec.d_in
            q_out = q_in / fanout
        elif spec.kind == "merge":
            demands[name] = q_in * spec.d_in * len(preds)
            q_out = q_in * len(preds)
        else:
            demands[name] = q_in * spec.d_in
            q_out = q_in * spec.spatial_ratio
        out[name] = RatePoint(features_per_clock=q_out * spec.d_out, d=spec.d_out)
    return demands, out


# ==========================================================================
# Per-node timing + join skew analysis
# ==========================================================================


@dataclasses.dataclass(frozen=True)
class NodeTiming:
    """Affine steady-state timing of one node's output stream:
    pixel m leaves at ``offset + (m+1)/q_out`` cycles."""

    name: str
    pass_cycles: Fraction  # C — cycles one pass over a pixel takes
    fill_cycles: Fraction  # sliding-window row banking before 1st output
    offset: Fraction  # stream intercept (cycles)
    q_in: Fraction  # pixels/clock consumed
    q_out: Fraction  # pixels/clock emitted


def pass_cycles(impl: LayerImpl) -> Fraction:
    """Cycles per pixel pass — mirrors schedule's discrete-event model."""
    if impl.mults == 0:
        return Fraction(max(1, impl.layer.d_in // max(1, impl.j)))
    return Fraction(impl.configs)


def fill_pixels(spec: LayerSpec) -> int:
    """Input pixels a sliding window banks before its first valid output
    ('same' padding: half a kernel of rows + half a row of columns).
    gap is excluded — its whole-frame aggregation is already captured by
    spatial decimation in the timing recurrence."""
    if spec.kind in ("conv", "dwconv", "pool") and max(spec.kernel) > 1:
        return (spec.kernel[0] - 1) // 2 * spec.in_hw[1] + (spec.kernel[1] - 1) // 2
    return 0


def frame_pixels(spec: LayerSpec) -> int:
    """Input pixels in one frame of ``spec``'s stream."""
    return spec.in_hw[0] * spec.in_hw[1]


def operand_offsets(
    graph: LayerGraph, name: str, timing: Dict[str, NodeTiming], q_in: Fraction
) -> List[Fraction]:
    """Per producer of ``name``, the offset at which its stream lets the
    node consume pixels at ``q_in``: the producer's own offset, except
    for a 'scale' join's gate, which arrives once a frame, after the
    frame's last trunk pixel (module docstring)."""
    spec = graph.spec(name)
    offsets = [timing[p].offset for p in graph.preds(name)]
    if spec.kind == "scale":
        offsets[1] += Fraction(frame_pixels(spec) - 1) / q_in
    return offsets


def decimation_keep(spec: LayerSpec) -> int:
    """1-in-keep pixel survival through this node (1 for non-decimating)."""
    ratio = 1 / spec.spatial_ratio
    if ratio <= 1:
        return 1
    if ratio.denominator != 1:
        raise GraphError(
            f"{spec.name}: non-integer decimation {ratio} unsupported in "
            f"graph timing (pad dims so in_px is a multiple of out_px)"
        )
    return int(ratio)


def compute_timing(
    graph: LayerGraph,
    impls: Dict[str, LayerImpl],
    input_rate: Fraction,
) -> Dict[str, NodeTiming]:
    """Solve the offset recurrence over topological order.

    Derivation: with fluid arrivals t_in(n) = o_in + (n+1)/q_in and
    output pixel m consuming input pixel m*keep + keep - 1,

      t_out(m) = t_in(m*keep + keep - 1) + C + fill
               = [o_in + C + fill] + (m+1)/(q_in/keep),

    so offsets simply accumulate C + fill along the longest path.
    """
    timing: Dict[str, NodeTiming] = {}
    for name in graph.topo_order():
        spec = graph.spec(name)
        preds = graph.preds(name)
        if not preds:
            o_in = Fraction(0)
            q_in = Fraction(input_rate) / spec.d_in
        else:
            q_in = timing[preds[0]].q_out
            o_in = max(operand_offsets(graph, name, timing, q_in))
        c = pass_cycles(impls[name])
        fill = Fraction(fill_pixels(spec)) / q_in if fill_pixels(spec) else Fraction(0)
        if spec.kind == "split":
            q_out = q_in / len(graph.succs(name))
        elif spec.kind == "merge":
            q_out = q_in * len(graph.preds(name))
        else:
            q_out = q_in * spec.spatial_ratio
        timing[name] = NodeTiming(
            name=name,
            pass_cycles=c,
            fill_cycles=fill,
            offset=o_in + c + fill,
            q_in=q_in,
            q_out=q_out,
        )
    return timing


@dataclasses.dataclass(frozen=True)
class JoinBuffer:
    """Analytically sized skew FIFO on one in-edge of a join node."""

    join: str
    src: str  # producer whose stream this FIFO parks
    skew_cycles: Fraction  # slowest-branch offset minus this branch's
    q: Fraction  # pixel rate on this edge (a gate edge: one pixel a frame)
    d: int  # channels per pixel on this edge
    bound_pixels: int  # max pixels resident (the analytical bound)
    width_bits: int  # FIFO word = one stream beat
    depth_words: int

    @property
    def bits(self) -> int:
        return self.width_bits * self.depth_words


def join_buffers(
    graph: LayerGraph,
    impls: Dict[str, LayerImpl],
    timing: Dict[str, NodeTiming],
) -> List[JoinBuffer]:
    """Size the skew FIFO on every join in-edge (see module docstring).

    Merge nodes (Multi-CLP lane re-interleave) get an extra *deal burst*
    term on every lane edge: the order-preserving merger drains lane k at
    the full frame rate only during lane k's turn, so a lane accumulates
    up to ceil(px * (R-1) / R) pixels while the other R-1 lanes' frames
    are being forwarded (px = pixels per frame on the edge).

    A 'scale' join's gate edge holds gates, not pixels: its skew is
    counted at the gate's own rate (one a frame), plus
    ``SCALE_GATE_SLOTS``.
    """
    buffers: List[JoinBuffer] = []
    for join in graph.joins():
        preds = graph.preds(join)
        spec = graph.spec(join)
        offsets = operand_offsets(graph, join, timing, timing[join].q_in)
        o_max = max(offsets)
        burst = 0
        if spec.kind == "merge":
            px = frame_pixels(spec)
            burst = math.ceil(Fraction(px * (len(preds) - 1), len(preds)))
        for i, p in enumerate(preds):
            skew = o_max - offsets[i]
            d = graph.spec(p).d_out
            q = timing[p].q_out
            slots = max(1, impls[join].p_raw)
            if spec.kind == "scale" and i == 1:
                slots = SCALE_GATE_SLOTS
            bound = math.floor(skew * q) + slots + burst
            r_edge = q * d  # features/clock on the edge
            lanes = max(1, math.ceil(r_edge))
            width = 8 * lanes
            depth = max(2, math.ceil(Fraction(bound * d, lanes)))
            buffers.append(
                JoinBuffer(
                    join=join,
                    src=p,
                    skew_cycles=skew,
                    q=q,
                    d=d,
                    bound_pixels=bound,
                    width_bits=width,
                    depth_words=depth,
                )
            )
    return buffers


def deal_buffers(
    graph: LayerGraph,
    impls: Dict[str, LayerImpl],
    timing: Dict[str, NodeTiming],
) -> List[JoinBuffer]:
    """Size the deal FIFO on every split -> lane edge.

    The round-robin frame splitter forwards at the full upstream pixel
    rate into one lane at a time while the lane drains at q / R, so the
    lane-side FIFO fills to ceil(px * (R-1) / R) pixels by the end of the
    lane's turn and drains over the next R-1 frames.  Reuses the
    ``JoinBuffer`` record (join = the lane, src = the splitter) so the
    resource model and ``stream_buffers`` price these FIFOs through the
    exact same machinery as join skew FIFOs.
    """
    buffers: List[JoinBuffer] = []
    for name in graph.topo_order():
        if graph.spec(name).kind != "split":
            continue
        lanes = graph.succs(name)
        spec = graph.spec(name)
        px = spec.out_hw[0] * spec.out_hw[1]
        burst = math.ceil(Fraction(px * (len(lanes) - 1), len(lanes)))
        d = spec.d_out
        for lane in lanes:
            q = timing[lane].q_in  # the dealt per-lane rate q / R
            bound = burst + max(1, impls[lane].p_raw)
            r_edge = q * d
            n_lanes = max(1, math.ceil(r_edge))
            width = 8 * n_lanes
            depth = max(2, math.ceil(Fraction(bound * d, n_lanes)))
            buffers.append(
                JoinBuffer(
                    join=lane,
                    src=name,
                    skew_cycles=Fraction(0),
                    q=q,
                    d=d,
                    bound_pixels=bound,
                    width_bits=width,
                    depth_words=depth,
                )
            )
    return buffers


# ==========================================================================
# DAG-aware DSE
# ==========================================================================


@dataclasses.dataclass(frozen=True)
class ImplPlan:
    """Per-node contract handed from the DSE to the kernel executor.

    Produced only by ``GraphPlan.kernel_plan()``; consumed only by the
    graph executor (``models/cnn.py``), which dispatches each node's
    Pallas call with ``tile`` and asserts the executed tiling matches it.
    ``demand`` is the decimation-adjusted rate this node must absorb
    (features/clock after every upstream stride/pool has thinned the
    stream) — the r its (j, h) was chosen against, not the network input
    rate.
    """

    name: str
    kind: str
    j: int  # input features/clock per phase (Eq. 9)
    h: int  # outputs time-multiplexed per unit
    p: int  # pixel phases after stride pruning
    demand: Fraction  # decimation-adjusted features/clock
    q_in: Fraction  # pixels/clock entering the node
    tile: Optional[TileChoice]  # None for kinds outside KERNEL_KINDS
    batch: Optional[int] = None  # serving batch the tile's bm was pinned to

    @property
    def has_kernel(self) -> bool:
        return self.tile is not None


@dataclasses.dataclass
class GraphPlan:
    """A complete hardware plan for a LayerGraph at one input rate.

    When planned with ``n_stages`` the plan additionally carries the
    multi-chip partition: ``stage_plan`` (the DAG cut) and
    ``stream_bufs`` (the FIFO on every cut-crossing edge).  The cut and
    the per-node (j, h) are mutually consistent by construction — the
    DP balances the mult counts the DSE selected, and because stream
    buffers are rate-transparent in steady state (they re-time, never
    re-rate), each node's demand is exactly the post-cut rate its
    (j, h) was chosen against: every stage independently satisfies
    Eq. 9 at the rate arriving over its cut.
    """

    graph: LayerGraph
    input_rate: Fraction
    scheme: str
    impls: "OrderedDict[str, LayerImpl]"
    demands: Dict[str, Fraction]
    out_points: Dict[str, RatePoint]
    timing: Dict[str, NodeTiming]
    buffers: List[JoinBuffer]
    stage_plan: Optional[GraphStagePlan] = None
    stream_bufs: Optional[List[StreamBuffer]] = None
    # Wire format of cut-crossing activations (str, or per-producer
    # mapping) — what every stream buffer's width was sized with.
    link_dtype: LinkDtype = "int8"
    # Multi-CLP replications applied before planning (core.replicate
    # records; empty for an unreplicated plan).  The serving engine uses
    # these to amortize lane service over the R frames a lane sees 1 of.
    replications: tuple = ()

    @property
    def total_mults(self) -> int:
        return sum(i.mults for i in self.impls.values())

    @property
    def total_units(self) -> int:
        return sum(i.units for i in self.impls.values())

    @property
    def infeasible_nodes(self) -> List[str]:
        """Nodes whose chosen capacity cannot absorb their demand — empty
        for scheme 'ours' by construction (Eq. 9 holds on every branch);
        [11]'s rounding can fail on awkward branch rates."""
        return [n for n, i in self.impls.items() if not i.feasible]

    @property
    def continuous_flow(self) -> bool:
        return not self.infeasible_nodes

    def buffer_for(self, join: str, src: str) -> JoinBuffer:
        for b in self.buffers:
            if b.join == join and b.src == src:
                return b
        raise KeyError((join, src))

    # -- multi-chip stage introspection (requires n_stages planning) ------

    def _require_stages(self) -> GraphStagePlan:
        if self.stage_plan is None:
            raise GraphError(
                "plan has no stage partition — call plan_graph(..., "
                "n_stages=S)"
            )
        return self.stage_plan

    def stage_mults(self) -> List[int]:
        """DSE-selected multiplier count per stage (what the cut balances)."""
        sp = self._require_stages()
        return [
            sum(self.impls[n].mults for n in sp.stage_nodes(s))
            for s in range(sp.n_stages)
        ]

    def stage_infeasible_nodes(self) -> List[List[str]]:
        """Per stage, the nodes whose capacity cannot absorb the post-cut
        rate — empty everywhere for scheme 'ours' (Eq. 9 holds on every
        branch at every cut); [11]'s rounding can fail on a stage whose
        cut lands on an awkward branch rate."""
        sp = self._require_stages()
        return [
            [n for n in sp.stage_nodes(s) if not self.impls[n].feasible]
            for s in range(sp.n_stages)
        ]

    def cut_rates(self) -> List[Fraction]:
        """Features/clock crossing each interior cut — the inter-chip
        link load (cut c separates stage c from stage c+1)."""
        sp = self._require_stages()
        rates = [Fraction(0)] * (sp.n_stages - 1)
        for sb in self.stream_bufs or []:
            for c in range(sb.src_stage, sb.dst_stage):
                rates[c] += sb.q * sb.d
        return rates

    @property
    def total_stream_bits(self) -> int:
        """Bits of inter-chip stream buffering the partition adds.
        Raises (like every stage accessor) on an unpartitioned plan —
        a silent 0 would read as 'the cut is free'."""
        self._require_stages()
        return sum(b.bits for b in self.stream_bufs or [])

    def stage_stream_bits(self) -> List[int]:
        """Cut-crossing buffer bits parked on each stage's chip (buffers
        live on the consuming stage) — what a ``bram_budget`` caps."""
        sp = self._require_stages()
        return list(stage_stream_bits(self.stream_bufs or [], sp.n_stages))

    def kernel_plan(
        self,
        *,
        dtype_bytes: int = 4,
        tpu: Optional[TPUSpec] = None,
        vmem_fraction: float = VMEM_FRACTION,
        batch: Optional[int] = None,
    ) -> "OrderedDict[str, ImplPlan]":
        """Lower this hardware plan to the executor's per-node contract.

        Every node gets an ``ImplPlan``; arithmetic nodes additionally
        carry the concrete Pallas tile derived from their (j, h) by
        ``core.tpu_tiles.select_tile_for_impl`` (j -> bk floor,
        d_out/h -> bn floor, grown to MXU alignment — capacity only ever
        increases, so Eq. 9 survives).  Keys preserve topological order.

        ``batch`` pins the pixel tile bm to a known serving micro-batch
        (the streaming engine passes its micro-batch size here): each
        tile's bm becomes a divisor of the batch-flattened runtime m, so
        the fcu kernels execute the *planned* bm instead of re-fitting
        it, and the executor asserts bm too (``ImplPlan.batch`` records
        the pin).  Without ``batch`` bm only bounds the runtime re-fit,
        exactly as before.

        ``tpu`` defaults to ``core.hw_specs.target_spec()``: the attached
        chip's spec (an unknown TPU kind raises), or TPU v5e — the
        explicit planning target — when JAX runs on another backend.
        """
        tpu = target_spec() if tpu is None else tpu
        plans: "OrderedDict[str, ImplPlan]" = OrderedDict()
        for name, impl in self.impls.items():
            spec = self.graph.spec(name)
            tile = None
            if spec.kind in KERNEL_KINDS:
                tile = select_tile_for_impl(
                    impl,
                    dtype_bytes=dtype_bytes,
                    spec=tpu,
                    vmem_fraction=vmem_fraction,
                    batch=batch,
                )
            plans[name] = ImplPlan(
                name=name,
                kind=spec.kind,
                j=impl.j,
                h=impl.h,
                p=impl.p,
                demand=impl.demand,
                q_in=self.timing[name].q_in,
                tile=tile,
                batch=batch,
            )
        return plans


def _plan_edge_traffic(plan: GraphPlan) -> Dict[Tuple[str, str], EdgeTraffic]:
    """Exact per-edge traffic from a solved plan — the q_in / d /
    absorbed-FIFO base that ``stream_buffers`` prices, handed to the
    budgeted DP so feasibility and pricing agree bit-for-bit."""
    graph = plan.graph
    out: Dict[Tuple[str, str], EdgeTraffic] = {}
    for dst in graph.topo_order():
        for src in graph.preds(dst):
            q = plan.timing[src].q_out  # a 'scale' gate: one pixel a frame
            try:
                base = plan.buffer_for(dst, src).bound_pixels
            except KeyError:
                base = 1
            out[(src, dst)] = EdgeTraffic(
                src=src,
                dst=dst,
                q=q,
                d=graph.spec(src).d_out,
                base_pixels=base,
            )
    return out


def plan_graph(
    graph: LayerGraph,
    input_rate: Fraction,
    *,
    scheme: str = "ours",
    prefer_large_h: bool = True,
    objective: str = "max_h",
    n_stages: Optional[int] = None,
    chain_cuts: bool = False,
    stage_cost_key: str = "mults",
    link_cycles: int = DEFAULT_LINK_CYCLES,
    link_dtype: LinkDtype = "int8",
    bram_budget=None,
    replicate=None,
    n_devices: Optional[int] = None,
) -> GraphPlan:
    """Select an implementation for every node of a DAG.

    The linear-graph specialization is *identical* to ``plan_network`` on
    the equivalent chain (property-tested): demands propagate through
    ``impl.rate_out`` exactly as the fluid recurrence, joins only add the
    operand-consistency constraint and the skew analysis.

    ``n_stages`` turns on multi-chip planning: the DAG is cut into that
    many contiguous-in-topo-order stages by the min-bottleneck /
    min-cut DP (``core.stage_partition.partition_graph``), balancing the
    *DSE-selected* per-node cost (``stage_cost_key``: 'mults' or
    'units'), and every cut-crossing edge — including skew FIFOs whose
    branch and join land in different stages — is sized as an
    inter-chip ``StreamBuffer`` with ``link_cycles`` of slack per chip
    boundary crossed.  ``chain_cuts=True`` restricts boundaries to
    single-stream positions (the chain-DP baseline the tables compare
    against).  The result lands in ``GraphPlan.stage_plan`` /
    ``stream_bufs``; the executor (``models.cnn.apply_staged``) and the
    resource model (``estimate_graph`` / ``estimate_stages``) both
    consume it.

    ``link_dtype`` sets the wire format of cut-crossing activations
    (``'int8'``/``'bf16'``/``'fp32'``, or a per-producer mapping) — it
    scales both the DP's cut weights and every stream buffer's width.
    ``bram_budget`` (bits per chip; scalar or one per stage) makes the
    partition buffer-aware: the DP only admits cuts whose parked stream
    bits fit each stage's chip, using the plan's exact edge traffic, so
    the ``stream_buffers`` it prices afterwards can never exceed the
    budget (asserted).  Raises ``ValueError`` when no partition fits.

    ``n_devices`` (with ``n_stages``) records a round-robin device
    placement on the stage plan — stage ``s`` on device ordinal
    ``s % n_devices`` — which the multi-device executor
    (``models.cnn.stage_functions(placement=True)`` /
    ``distributed.device_pipeline.DevicePipeline``) resolves against
    the live device list at run time.  Placement is advisory metadata:
    it changes where stages execute, never what they compute.

    ``replicate`` turns on Multi-CLP bottleneck replication *before*
    planning: a ``(node, R)`` pair, a ``{node: R}`` mapping, or a bare
    ``R`` (auto-select the max-mults bottleneck).  The named node is
    cloned R ways behind a round-robin frame splitter and an
    order-preserving merger (``core.replicate``), the DSE sees each lane
    at demand rate/R, and the min-bottleneck DP is re-run over the
    replicated graph — so stage balance is no longer capped by the
    dominant layer.  The applied ``Replication`` records land in
    ``GraphPlan.replications``.
    """
    if n_devices is not None and n_stages is None:
        raise GraphError("n_devices= requires n_stages= (placement is per stage)")
    replications: tuple = ()
    if replicate is not None:
        from .replicate import apply_replications

        graph, replications = apply_replications(
            graph, replicate, input_rate=input_rate, scheme=scheme
        )
    demands, out_points = propagate_graph(graph, input_rate)
    impls: "OrderedDict[str, LayerImpl]" = OrderedDict()
    for name in graph.topo_order():
        impls[name] = select_impl(
            graph.spec(name),
            demands[name],
            scheme=scheme,
            prefer_large_h=prefer_large_h,
            objective=objective,
        )
    timing = compute_timing(graph, impls, input_rate)
    plan = GraphPlan(
        graph=graph,
        input_rate=Fraction(input_rate),
        scheme=scheme,
        impls=impls,
        demands=demands,
        out_points=out_points,
        timing=timing,
        buffers=join_buffers(graph, impls, timing)
        + deal_buffers(graph, impls, timing),
        link_dtype=link_dtype,
        replications=replications,
    )
    if n_stages is not None:
        plan.stage_plan = partition_graph(
            graph,
            plan_node_costs(plan, stage_cost_key),
            n_stages,
            chain_cuts=chain_cuts,
            link_dtype=link_dtype,
            bram_budget=bram_budget,
            edge_traffic=(
                _plan_edge_traffic(plan) if bram_budget is not None else None
            ),
            link_cycles=link_cycles,
        )
        if n_devices is not None:
            plan.stage_plan = dataclasses.replace(
                plan.stage_plan,
                placement=round_robin_placement(n_stages, n_devices),
            )
        plan.stream_bufs = stream_buffers(
            plan, plan.stage_plan, link_cycles=link_cycles, link_dtype=link_dtype
        )
        if plan.stage_plan.bram_budget is not None:
            parked = stage_stream_bits(plan.stream_bufs, n_stages)
            if tuple(parked) != plan.stage_plan.stage_buffer_bits:
                raise GraphError(
                    f"budgeted DP parked bits {plan.stage_plan.stage_buffer_bits}"
                    f" != priced stream buffers {tuple(parked)} — "
                    f"edge_buffer_geometry drifted from stream_buffers"
                )
    return plan
