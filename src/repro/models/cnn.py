"""Unified CNN inference machinery: execute a ``LayerGraph`` in JAX.

Every CNN family in the repo (MobileNetV1/V2, ResNet-18/34) describes
itself **once**, as the ``LayerSpec`` DAG consumed by the data-rate DSE
(core.graph).  This module is the other half of that contract: a generic
interpreter that runs the *same* graph as a JAX network —

  * ``init_graph_params``  — He-init weights + folded-BN bias per node,
  * ``apply_graph``        — topological forward pass (NHWC),
  * ``apply_staged``       — the multi-chip execution of a stage
    partition: each stage's subgraph jitted separately, cut-crossing
    activations (including skew-buffered shortcut tensors) threaded
    across stage boundaries,
  * ``stage_functions``    — the per-stage callables underneath
    ``staged_forward``, exposed individually so the streaming serving
    engine (serving/cnn_stream.py) can keep one micro-batch per stage
    in flight,
  * ``quantize_params`` / ``apply_int8`` — the paper's 8-bit datapath,
  * ``default_impls`` / ``kernel_impls`` — XLA ops vs the Pallas KPU /
    FCU / DW kernels, swappable per layer kind, with node-keyed
    ``overrides`` for user-supplied per-node implementations.

Because topology and inference share one description they cannot drift:
``apply_graph(check=True)`` re-derives each node's output shape and MAC
count from the live arrays and asserts they equal the spec's analytic
values (``LayerSpec.total_macs`` — the numbers ``core.flops.graph_macs``
feeds to the DSE and the benchmark tables).

Plan-threading contract (the rate-matched execution path):

  ``core.graph.plan_graph(...).kernel_plan()`` is the producer: a
  per-node ``ImplPlan`` table mapping each arithmetic node to the Pallas
  tile derived from *its own* DSE choice (j, h, decimation-adjusted
  demand).  This module is the consumer: ``apply_graph(plan=...)`` (or
  ``kernel_impls(plan=...)`` directly) builds one kernel impl per node,
  keyed by node *name*, each pinned to its planned tile — no single
  global ``rate`` is involved on this path.  Invariants asserted at
  apply time (trace time — free under jit): every graph node has a plan
  entry; every planned kernel reports the tile it executed via the ops
  adapters' ``record`` callback; the executed (bk, bn) equals the
  plan's; tile dims divide the live array dims.  Violations raise
  ``GraphExecutionError``, same as the shape/MAC cross-checks.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Mapping, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.dse import NON_ARITH_KINDS
from repro.core.graph import JOIN_KINDS, ImplPlan, LayerGraph
from repro.core.rate import LayerSpec
from repro.core.stage_partition import resolve_link_dtype
from repro.nn.quant import dequantize_link, fake_quant_link, quantize_link

Impl = Callable[..., jax.Array]
Params = Dict[str, Dict[str, jax.Array]]

# Weighted kinds — the complement of the DSE-owned partition
# (core.dse.NON_ARITH_KINDS).  Membership checks below go through
# NON_ARITH_KINDS directly so a kind added on the DSE side cannot be
# silently treated as parameterless wiring here: it reaches
# ``_weight_shape``, which raises for layouts it does not know.
ARITH_KINDS = ("conv", "dwconv", "pointwise", "dense")


def _is_arith(spec: LayerSpec) -> bool:
    return spec.kind not in NON_ARITH_KINDS


class GraphExecutionError(ValueError):
    """The executable network disagrees with its LayerGraph description."""


_ACTIVATIONS: Dict[str, Callable[[jax.Array], jax.Array]] = {
    "none": lambda x: x,
    "relu": jax.nn.relu,
    "relu6": lambda x: jnp.clip(x, 0.0, 6.0),
}


# ==========================================================================
# Default (XLA) implementations of the arithmetic kinds
# ==========================================================================


def _conv(x: jax.Array, w: jax.Array, stride: int) -> jax.Array:
    return jax.lax.conv_general_dilated(
        x,
        w,
        window_strides=(stride, stride),
        padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )


def _dwconv(x: jax.Array, w: jax.Array, stride: int) -> jax.Array:
    return jax.lax.conv_general_dilated(
        x,
        w,
        window_strides=(stride, stride),
        padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=x.shape[-1],
    )


def _pointwise(x: jax.Array, w: jax.Array) -> jax.Array:
    return jnp.einsum("bhwc,cd->bhwd", x, w)


def _dense(x: jax.Array, w: jax.Array) -> jax.Array:
    return x @ w


def default_impls() -> Dict[str, Impl]:
    """Pure-XLA implementations (the lax fallback; runs anywhere)."""
    return {
        "conv": _conv,
        "dwconv": _dwconv,
        "pointwise": _pointwise,
        "dense": _dense,
    }


def kernel_impls(
    *,
    rate=None,
    plan: Optional[Mapping[str, ImplPlan]] = None,
    executed: Optional[Dict[str, Dict[str, int]]] = None,
) -> Dict[str, Impl]:
    """Pallas-kernel-backed implementations (KPU / DW / FCU).

    Imported lazily so graph-only callers never pay for (or break on)
    the Pallas stack.  Where the kernels run is the backend's choice
    (``kernels.common.pallas_call``): Mosaic-compiled on a TPU, the
    Pallas interpreter elsewhere.

    Without ``plan`` this is the **uniform** path: four kind-level impls
    whose tiles come from ``select_tile`` under one global ``rate``
    (or the max-intensity tile when ``rate`` is None).

    With ``plan`` (a ``GraphPlan.kernel_plan()`` table) this is the
    **rate-matched** path: the returned dict additionally carries one
    impl per arithmetic *node name*, each pinned to that node's planned
    tile.  ``apply_graph`` dispatches name-first, so every node runs its
    own (j, h)-derived tiling.  When ``executed`` is given, each node
    impl records the tile it actually ran into ``executed[name]`` at
    trace time (``apply_graph(plan=...)`` uses this for its per-node
    plan-vs-executed assertion).
    """
    from repro.kernels.dw_conv.ops import dw_conv_impl
    from repro.kernels.fcu_matmul.ops import dense_impl, pointwise_impl
    from repro.kernels.kpu_conv.ops import conv_impl

    factories = {
        "conv": conv_impl,
        "dwconv": dw_conv_impl,
        "pointwise": pointwise_impl,
        "dense": dense_impl,
    }
    table: Dict[str, Impl] = {
        kind: make(rate=rate) for kind, make in factories.items()
    }
    if plan is None:
        return table
    for name, node_plan in plan.items():
        if not node_plan.has_kernel:
            continue  # pool / add / gap / concat: wiring, no kernel
        if name in factories:
            raise GraphExecutionError(
                f"node name {name!r} collides with an impl kind key"
            )
        record = None
        if executed is not None:
            record = _tile_recorder(executed, name)
        table[name] = factories[node_plan.kind](tile=node_plan.tile, record=record)
    return table


def _tile_recorder(executed: Dict[str, Dict[str, int]], name: str):
    def record(**tile):
        executed[name] = tile
    return record


# ==========================================================================
# Parameters
# ==========================================================================


def _weight_shape(spec: LayerSpec) -> tuple:
    if spec.kind == "conv":
        return (*spec.kernel, spec.d_in, spec.d_out)
    if spec.kind == "dwconv":
        # HWIO for grouped conv: I = 1 (per-group), O = C * multiplier
        return (*spec.kernel, 1, spec.d_in * spec.channel_multiplier)
    if spec.kind in ("pointwise", "dense"):
        return (spec.d_in, spec.d_out)
    raise GraphExecutionError(f"{spec.name}: no weight layout for kind {spec.kind!r}")


def _fan_in(spec: LayerSpec) -> int:
    if spec.kind == "conv":
        return spec.d_in * spec.k_taps
    if spec.kind == "dwconv":
        return spec.k_taps
    return spec.d_in


def init_graph_params(
    graph: LayerGraph, rng: jax.Array, dtype=jnp.float32
) -> Params:
    """He-init weights + folded-BN bias for every arithmetic node."""
    params: Params = {}
    for name in graph.topo_order():
        spec = graph.spec(name)
        if not _is_arith(spec):
            continue
        rng, k1 = jax.random.split(rng)
        w = jax.random.normal(k1, _weight_shape(spec), dtype) * np.sqrt(
            2.0 / _fan_in(spec)
        )
        params[name] = {"w": w, "b": jnp.zeros((spec.d_out,), dtype)}
    return params


# ==========================================================================
# Forward pass
# ==========================================================================


def _merge_lanes(operands: List[jax.Array]) -> jax.Array:
    """Order-preserving re-interleave of R dealt lane streams: lane k's
    frame i becomes output frame i*R + k — the exact inverse of the
    consumer-side ``x[k::R]`` deal, so split -> lanes -> merge is the
    identity on the batch (bit-exact; conv is batch-parallel)."""
    r = len(operands)
    n = sum(o.shape[0] for o in operands)
    out = jnp.zeros((n, *operands[0].shape[1:]), operands[0].dtype)
    for k, o in enumerate(operands):
        out = out.at[k::r].set(o)
    return out


def _node_forward(
    spec: LayerSpec,
    operands: List[jax.Array],
    p: Optional[Dict[str, jax.Array]],
    impls: Dict[str, Impl],
) -> jax.Array:
    # LayerGraph.add enforces this too; re-assert so a graph built any
    # other way cannot silently drop an in-edge the DSE planned for.
    if len(operands) > 1 and spec.kind not in JOIN_KINDS and spec.kind != "merge":
        raise GraphExecutionError(
            f"{spec.name}: kind {spec.kind!r} got {len(operands)} operands"
        )
    x = operands[0]
    # per-node impls (rate-matched plans) take precedence over kind-level
    # defaults; kernel_impls(plan=...) registers them under the node name.
    def fn(kind):
        return impls.get(spec.name) or impls[kind]

    if spec.kind == "conv":
        y = fn("conv")(x, p["w"], spec.stride[0]) + p["b"]
    elif spec.kind == "dwconv":
        y = fn("dwconv")(x, p["w"], spec.stride[0]) + p["b"]
    elif spec.kind == "pointwise":
        y = fn("pointwise")(x, p["w"]) + p["b"]
    elif spec.kind == "dense":
        y = fn("dense")(x, p["w"]) + p["b"]
    elif spec.kind == "pool":
        y = jax.lax.reduce_window(
            x,
            -jnp.inf,
            jax.lax.max,
            window_dimensions=(1, *spec.kernel, 1),
            window_strides=(1, *spec.stride, 1),
            padding="SAME",
        )
    elif spec.kind == "gap":
        y = jnp.mean(x, axis=(1, 2))
    elif spec.kind == "add":
        y = x
        for other in operands[1:]:
            y = y + other
    elif spec.kind == "concat":
        y = jnp.concatenate(operands, axis=-1)
    elif spec.kind == "split":
        # Multi-CLP round-robin frame splitter (core.replicate): pure
        # wiring — each lane consumer takes its dealt batch subsequence
        # (the slicing happens consumer-side in ``_run_nodes``).
        y = x
    elif spec.kind == "merge":
        y = _merge_lanes(operands)
    else:
        raise GraphExecutionError(f"{spec.name}: unknown kind {spec.kind!r}")
    try:
        act = _ACTIVATIONS[spec.activation]
    except KeyError:
        raise GraphExecutionError(
            f"{spec.name}: unknown activation {spec.activation!r}"
        ) from None
    return act(y)


def _macs_from_arrays(
    spec: LayerSpec, p: Optional[Dict[str, jax.Array]], y: jax.Array
) -> int:
    """Re-derive the node's MAC count from live array shapes alone."""
    if not _is_arith(spec):
        return 0
    out_px = y.shape[1] * y.shape[2] if y.ndim == 4 else 1
    w = p["w"]
    if spec.kind == "conv":
        kh, kw, ci, co = w.shape
        return kh * kw * ci * co * out_px
    if spec.kind == "dwconv":
        kh, kw, _, co = w.shape
        return kh * kw * co * out_px
    ci, co = w.shape  # pointwise / dense
    return ci * co * out_px


def _check_node(
    spec: LayerSpec, p: Optional[Dict[str, jax.Array]], y: jax.Array
) -> None:
    n = y.shape[0]
    if spec.kind in ("gap", "dense"):
        expect = (n, spec.d_out)
    elif spec.kind in ("split", "merge") and y.ndim == 2:
        expect = (n, spec.d_out)  # replication wiring on the post-gap vector
    else:
        expect = (n, *spec.out_hw, spec.d_out)
    if tuple(y.shape) != expect:
        raise GraphExecutionError(
            f"{spec.name}: executable shape {tuple(y.shape)} != "
            f"LayerGraph shape {expect}"
        )
    macs = _macs_from_arrays(spec, p, y)
    if macs != spec.total_macs:
        raise GraphExecutionError(
            f"{spec.name}: executable MACs {macs} != "
            f"LayerSpec.total_macs {spec.total_macs}"
        )


def _check_planned_tile(
    spec: LayerSpec,
    node_plan: Optional[ImplPlan],
    got: Optional[Dict[str, int]],
) -> None:
    """Assert one node's *executed* tile equals its ``ImplPlan`` tile.

    ``got`` is what the ops adapter's ``record`` callback reported at
    trace time.  The pixel tile bm is allowed to re-fit the runtime m
    (batch is flattened into it); the channel tiles (bk, bn) — the
    paper's j and d_out/h images — must match the plan exactly and
    divide the live array dims.  When the plan was pinned to a serving
    batch (``kernel_plan(batch=B)`` — ``ImplPlan.batch`` set), the fcu
    kinds (and convs planned on the im2col route, which run the FCU)
    additionally must execute the planned bm on the planned m:
    the micro-batcher promised that shape, so a mismatch is a serving
    bug, not a legal re-fit.
    """
    if node_plan is None:
        raise GraphExecutionError(f"{spec.name}: node missing from the kernel plan")
    if not node_plan.has_kernel:
        return
    if got is None:
        raise GraphExecutionError(
            f"{spec.name}: planned kernel did not report an executed tile"
        )
    t = node_plan.tile
    if (got.get("bk"), got.get("bn")) != (t.bk, t.bn):
        raise GraphExecutionError(
            f"{spec.name}: executed tile (bk={got.get('bk')}, "
            f"bn={got.get('bn')}) != ImplPlan tile (bk={t.bk}, bn={t.bn})"
        )
    d_in, d_out = got.get("d_in"), got.get("d_out")
    if (d_in, d_out) != (spec.d_in, spec.d_out):
        raise GraphExecutionError(
            f"{spec.name}: kernel saw dims ({d_in}, {d_out}) != LayerSpec "
            f"({spec.d_in}, {spec.d_out})"
        )
    if d_in % t.bk or (spec.kind != "dwconv" and d_out % t.bn):
        raise GraphExecutionError(
            f"{spec.name}: planned tile (bk={t.bk}, bn={t.bn}) does not "
            f"divide live dims ({d_in}, {d_out})"
        )
    if node_plan.batch is not None and (
        spec.kind in ("pointwise", "dense") or t.im2col
    ):
        want_m = node_plan.batch * spec.out_hw[0] * spec.out_hw[1]
        if got.get("m") != want_m:
            raise GraphExecutionError(
                f"{spec.name}: plan pinned to batch {node_plan.batch} "
                f"(m={want_m}) but the kernel saw m={got.get('m')} — "
                f"micro-batch the inputs to the planned size"
            )
        if got.get("bm") != t.bm:
            raise GraphExecutionError(
                f"{spec.name}: executed bm={got.get('bm')} != batch-pinned "
                f"plan bm={t.bm}"
            )


def _check_single_stream(graph: LayerGraph) -> str:
    """Require one input and one output node; return the output's name."""
    inputs = graph.input_nodes
    outputs = graph.output_nodes
    if len(inputs) != 1 or len(outputs) != 1:
        raise GraphExecutionError(
            f"the executor needs a single-input/single-output graph, got "
            f"inputs={inputs}, outputs={outputs}"
        )
    return outputs[0]


def _build_table(
    *,
    impls: Optional[Dict[str, Impl]],
    plan: Optional[Mapping[str, ImplPlan]],
    overrides: Optional[Mapping[str, Impl]],
    graph: LayerGraph,
    executed: Dict[str, Dict[str, int]],
) -> Dict[str, Impl]:
    """Assemble the dispatch table: kind-level defaults, then plan-derived
    per-node kernels, then kind-level ``impls``, then node-keyed user
    ``overrides`` (which always win — they are validated against the
    graph so a typoed node name fails loudly)."""
    table = default_impls()
    if plan is not None:
        table.update(kernel_impls(plan=plan, executed=executed))
    if impls:
        table.update(impls)
    if overrides:
        unknown = [n for n in overrides if n not in graph]
        if unknown:
            raise GraphExecutionError(f"overrides for unknown nodes: {unknown}")
        bad = [n for n in overrides if not _is_arith(graph.spec(n))]
        if bad:
            raise GraphExecutionError(
                f"overrides for non-arithmetic (wiring) nodes: {bad}"
            )
        table.update(overrides)
    return table


# --------------------------------------------------------------------------
# Quantized cut crossings (the link_dtype wire format, executor side)
# --------------------------------------------------------------------------


def cut_edge_dtypes(
    graph: LayerGraph, partition, link_dtype="int8"
) -> Dict[tuple, str]:
    """{(src, dst): dtype} for every cut-crossing edge of ``partition``
    narrower than fp32 — the executor-side mirror of the ``link_dtype``
    the DSE priced ``StreamBuffer`` widths with.  fp32 edges are
    omitted: a full-width wire needs no transform, so the fp32 path is
    bit-identical to no link quantization at all.
    """
    if hasattr(partition, "stage_plan"):  # a GraphPlan from n_stages=
        partition = partition.stage_plan
    stage_of = partition.stage_index()
    out: Dict[tuple, str] = {}
    for v in graph.topo_order():
        for u in graph.preds(v):
            if stage_of[u] != stage_of[v]:
                dt = resolve_link_dtype(link_dtype, u)
                if dt != "fp32":
                    out[(u, v)] = dt
    return out


def _resolve_link_quant(link_quant, graph, partition) -> Dict[tuple, str]:
    """Normalize the executor's ``link_quant`` option to an edge map.

    ``None`` -> off; ``True`` -> the partition plan's own ``link_dtype``
    (a ``GraphPlan``; plain partitions default to int8); a dtype str or
    per-producer {src: dtype} -> resolved over the cut edges; an
    edge-keyed {(src, dst): dtype} dict passes through.
    """
    if link_quant is None:
        return {}
    if link_quant is True:
        link_quant = getattr(partition, "link_dtype", "int8")
    if isinstance(link_quant, dict) and any(
        isinstance(k, tuple) for k in link_quant
    ):
        return {k: v for k, v in link_quant.items() if v != "fp32"}
    return cut_edge_dtypes(graph, partition, link_quant)


def _link_encode(x: jax.Array, dtype: str):
    """Producer side of a quantized crossing: the wire payload exported
    into the boundary dict (an int8 {"__q__", "__s__"} pytree, or a bare
    bf16 cast — both jit-safe boundary values)."""
    if dtype == "int8":
        return quantize_link(x)
    if dtype == "bf16":
        return x.astype(jnp.bfloat16)
    return x


def _link_decode(v, dtype: str, out_dtype=jnp.float32):
    """Consumer side — and, on the monolithic reference path where the
    operand was never encoded, the in-graph quantize-dequantize round
    trip.  Staged decode and monolithic fake-quant produce identical
    values, which is what makes staged int8 bit-exact vs monolithic."""
    if isinstance(v, dict):
        return dequantize_link(v, dtype=out_dtype)
    if dtype == "int8":
        return fake_quant_link(v, dtype=out_dtype)
    if dtype == "bf16":
        return v.astype(jnp.bfloat16).astype(out_dtype)
    return v


def _run_nodes(
    graph: LayerGraph,
    names,
    values: Dict[str, jax.Array],
    params: Params,
    table: Dict[str, Impl],
    *,
    x_input: Optional[jax.Array] = None,
    plan: Optional[Mapping[str, ImplPlan]] = None,
    executed: Optional[Dict[str, Dict[str, int]]] = None,
    overridden=frozenset(),
    check: bool = True,
    link_quant: Optional[Mapping[tuple, str]] = None,
) -> None:
    """Execute ``names`` in order, reading/writing ``values``.

    The shared inner loop of ``apply_graph`` (all nodes at once) and
    ``apply_staged`` (one stage's subgraph at a time): per-node forward,
    shape/MAC cross-check, and — on the rate-matched path — the
    executed-tile-==-plan assertion.  Nodes named in ``overridden`` run
    a user-supplied impl: they are exempt from the tile assertion
    unless the override recorded into ``executed`` itself (the shared
    dict), in which case the record is still validated.

    ``link_quant`` maps cut-crossing edges (src, dst) to a wire dtype:
    an operand read over such an edge is decoded (staged path — the
    boundary carries the encoded payload) or fake-quantized in place
    (monolithic path — same values, so the two stay comparable).  The
    transform applies *before* split-lane slicing: the producer encodes
    its full stream once, with one scale.
    """
    executed = executed if executed is not None else {}
    for name in names:
        spec = graph.spec(name)
        preds = graph.preds(name)
        if preds:
            missing = [p for p in preds if p not in values]
            if missing:
                raise GraphExecutionError(
                    f"{name}: operands {missing} not materialized — "
                    f"producer scheduled in a later stage?"
                )
            operands = []
            for pr in preds:
                v = values[pr]
                if link_quant:
                    dt = link_quant.get((pr, name))
                    if dt is not None:
                        v = _link_decode(v, dt)
                if graph.spec(pr).kind == "split":
                    # Replication lane: consume the dealt subsequence of
                    # the split stream (this lane's slot in deal order).
                    lanes = graph.succs(pr)
                    v = v[lanes.index(name) :: len(lanes)]
                operands.append(v)
        else:
            if x_input is None:
                raise GraphExecutionError(
                    f"{name}: source node executed outside the input stage"
                )
            operands = [x_input]
        p = params.get(name)
        if _is_arith(spec) and p is None:
            raise GraphExecutionError(f"{name}: missing parameters")
        y = _node_forward(spec, operands, p, table)
        if check:
            _check_node(spec, p, y)
        if plan is not None:
            if name in overridden and executed.get(name) is None:
                pass  # user-supplied impl; no record => no tile claim
            else:
                _check_planned_tile(spec, plan.get(name), executed.get(name))
        values[name] = y


def apply_graph(
    params: Params,
    x: jax.Array,
    graph: LayerGraph,
    *,
    impls: Optional[Dict[str, Impl]] = None,
    plan: Optional[Mapping[str, ImplPlan]] = None,
    overrides: Optional[Mapping[str, Impl]] = None,
    executed: Optional[Dict[str, Dict[str, int]]] = None,
    dtype=jnp.float32,
    check: bool = True,
    link_quant: Optional[Mapping[tuple, str]] = None,
) -> jax.Array:
    """Forward pass of a LayerGraph network.  ``x``: [N, H, W, d_in].

    ``impls`` overrides any of {'conv', 'dwconv', 'pointwise', 'dense'}
    with kernel-backed implementations (see ``kernel_impls``).  With
    ``check=True`` (trace-time only — free under jit) every node's output
    shape and MAC count are asserted against its ``LayerSpec``.

    ``plan`` switches to rate-matched execution: a per-node ``ImplPlan``
    table (``core.graph.GraphPlan.kernel_plan()``) from which one Pallas
    impl per arithmetic node is built (``kernel_impls(plan=...)``),
    each dispatching its node's own
    (j, h)-derived tile.  After each planned node executes, the tile the
    kernel reported is asserted equal to the plan's (see
    ``_check_planned_tile``) — the executable network provably follows
    the DSE.  With ``plan``, the per-node impls win on every arithmetic
    node (``kernel_plan`` tiles all of them), so kind-level ``impls``
    overrides are shadowed there.

    ``overrides`` is the first-class node-keyed escape hatch: a mapping
    from node *name* to an impl with the kind-level calling convention.
    Overrides win over everything, are validated against the graph
    (unknown or wiring-node names raise), and are exempt from the
    executed-tile assertion — unless the override records into the
    shared ``executed`` dict (pass the same dict to ``kernel_impls``),
    in which case its record is validated like any planned kernel's.
    ``executed``, when given, receives each node's executed tile (an
    out-param for introspection; a fresh private dict is used
    otherwise).

    ``link_quant`` — an edge-keyed {(src, dst): dtype} map (e.g. from
    ``cut_edge_dtypes``) — fake-quantizes each mapped operand read in
    place, making this the monolithic *reference* for staged execution
    with quantized cut crossings: identical transform at identical
    edges, so the staged int8 path can be compared bit-exactly.
    """
    out_name = _check_single_stream(graph)
    if executed is None:
        executed = {}
    table = _build_table(
        impls=impls,
        plan=plan,
        overrides=overrides,
        graph=graph,
        executed=executed,
    )
    values: Dict[str, jax.Array] = {}
    _run_nodes(
        graph,
        graph.topo_order(),
        values,
        params,
        table,
        x_input=x.astype(dtype),
        plan=plan,
        executed=executed,
        overridden=frozenset(overrides or ()),
        check=check,
        link_quant=link_quant,
    )
    return values[out_name]


# ==========================================================================
# Staged (multi-chip) execution of a stage partition
# ==========================================================================


def resolve_stage_devices(placement, n_stages: int, partition=None):
    """Normalize a ``placement`` option to a per-stage device tuple.

    Accepted forms (``None``/``False`` mean single-host execution —
    no transfers, exactly the pre-placement behavior):

    * ``True`` — the partition's recorded ``placement`` ordinals
      (``GraphStagePlan.placement``, e.g. from ``plan_graph(...,
      n_devices=)``) when present, else one device per stage: stage
      ``s`` on ``jax.devices()[s]``.
    * an ``int`` n — round-robin over the first n local devices.
    * a sequence of device *ordinals* — indices into ``jax.devices()``,
      cycled when shorter than the stage count (``(0,)`` puts every
      stage on device 0).
    * a sequence of ``jax.Device`` objects — used round-robin.

    Asking for more distinct devices than exist raises
    ``GraphExecutionError``: stages never fold silently onto fewer
    devices.  Co-resident stages are placed only when asked for by
    name (repeated ordinals, ``n_devices=`` at planning, or an int).
    """
    if placement is None or placement is False:
        return None
    devs = jax.devices()
    if placement is True:
        recorded = getattr(partition, "placement", None)
        placement = tuple(recorded or range(n_stages))
    if isinstance(placement, int):
        if not 1 <= placement <= len(devs):
            raise GraphExecutionError(
                f"placement over {placement} devices, but "
                f"{len(devs)} exist"
            )
        placement = tuple(range(placement))
    seq = list(placement)
    if not seq:
        raise GraphExecutionError("placement sequence is empty")
    if all(isinstance(p, int) for p in seq):
        bad = [p for p in seq if not 0 <= p < len(devs)]
        if bad:
            raise GraphExecutionError(
                f"placement asks for device ordinals {bad}, but only "
                f"{len(devs)} device(s) exist (stages {n_stages}; pass "
                f"repeated ordinals to co-locate stages)"
            )
        seq = [devs[p] for p in seq]
    return tuple(seq[s % len(seq)] for s in range(n_stages))


def _pipeline_cache_get(cache, refs, knobs):
    """Identity-keyed memo lookup for compiled ``StagePipeline``s.

    ``refs`` are compared by object identity (graphs, partitions, impl
    tables and plans are not hashable); the entry stores strong
    references to them, and a hit additionally verifies every ref with
    ``is`` — so id() reuse after garbage collection can only produce a
    miss, never a stale pipeline.  Returns ``(key, hit_or_None)``.
    """
    key = (tuple(map(id, refs)), knobs)
    ent = cache.get(key)
    if ent is not None and all(a is b for a, b in zip(ent[0], refs)):
        return key, ent[1]
    return key, None


def _stage_io(
    graph: LayerGraph, partition, out_name: str
) -> tuple:
    """Per-stage imports/exports of a ``GraphStagePlan``.

    ``imports[s]``: node names produced in an earlier stage that stage
    ``s`` consumes (the cut-crossing activations — for a join whose
    shortcut operand lives upstream, this is the skew-buffered shortcut
    tensor).  ``exports[s]``: names stage ``s`` must emit across its
    outgoing cut (plus the graph output on the final stage).
    """
    stage_of = partition.stage_index()
    n_stages = partition.n_stages
    imports = [set() for _ in range(n_stages)]
    exports = [set() for _ in range(n_stages)]
    for v in graph.topo_order():
        for u in graph.preds(v):
            if stage_of[u] != stage_of[v]:
                imports[stage_of[v]].add(u)
                exports[stage_of[u]].add(u)
    exports[stage_of[out_name]].add(out_name)
    return imports, exports


def stage_functions(
    graph: LayerGraph,
    *,
    partition,
    impls: Optional[Dict[str, Impl]] = None,
    plan: Optional[Mapping[str, ImplPlan]] = None,
    overrides: Optional[Mapping[str, Impl]] = None,
    executed: Optional[Dict[str, Dict[str, int]]] = None,
    check: bool = True,
    jit: bool = True,
    link_quant=None,
    placement=None,
    cache: Optional[dict] = None,
) -> "StagePipeline":
    """Compile the per-stage callables of a stage partition — the unit
    the streaming serving engine (``serving/cnn_stream.py``) pipelines.

    ``staged_forward`` runs these stages back-to-back for one input;
    the serving engine instead keeps one micro-batch *per stage* in
    flight, so it needs the stages as separately drivable functions.
    Each stage fn has signature ``fn(stage_params, boundary_in, x)``
    where ``boundary_in`` maps the stage's imported (cut-crossing) node
    names to tensors and ``x`` is the network input for stage 0 (None
    elsewhere); it returns the dict of tensors the stage exports across
    its outgoing cut (plus the graph output on the final stage).  Each
    fn is wrapped in ``jax.jit`` exactly once (``jit=True``), so a
    serving loop hits the jit cache every tick.

    ``link_quant`` turns on quantized cut crossings (opt-in — off, the
    boundary carries full-precision tensors exactly as before): the
    producing stage encodes each crossing activation to its wire dtype
    (``_link_encode``) and every consuming stage decodes it inside its
    own jitted fn, so what moves between stages is what the plan's
    ``StreamBuffer`` widths were priced for.  Accepts ``True`` (use the
    plan's ``link_dtype``), a dtype str, a per-producer {src: dtype}, or
    an edge-keyed {(src, dst): dtype} map.  The graph output is never
    encoded (it crosses no cut).

    ``placement`` turns on multi-device execution (see
    ``resolve_stage_devices`` for the accepted forms): each stage's
    params live resident on its device, every call moves the stage's
    imported boundary tensors there (``jax.device_put``, donating the
    source buffer when no later stage imports it), and JAX's committed-
    input rule makes each stage's jitted fn compute on its own device —
    so a driver that dispatches stages without blocking
    (``distributed.device_pipeline.DevicePipeline``) genuinely overlaps
    micro-batches on silicon.  With ``link_quant`` the transfers carry
    the int8 wire payloads, so device-to-device traffic shrinks exactly
    as the priced links predict.

    ``cache`` (a plain dict the caller owns) memoizes the compiled
    pipeline on the identity of (graph, partition, plan, impls,
    overrides, link_quant, placement) plus the check/jit
    knobs, so repeated one-shot calls (``apply_staged`` via
    ``registry.CNNApi``) hit the per-stage jit cache instead of
    retracing every stage per call.  Skipped when ``executed`` is given
    — a memoized pipeline cannot re-fill a caller's out-param.
    """
    out_name = _check_single_stream(graph)
    cache_key = cache_refs = None
    if cache is not None and executed is None:
        cache_refs = (graph, partition, plan, impls, overrides, link_quant, placement)
        cache_key, hit = _pipeline_cache_get(
            cache, cache_refs, (check, jit)
        )
        if hit is not None:
            return hit
    if hasattr(partition, "stage_plan"):  # a GraphPlan from n_stages=
        if partition.stage_plan is None:
            raise GraphExecutionError(
                "GraphPlan has no stage partition — plan with n_stages=S"
            )
        qmap = _resolve_link_quant(link_quant, graph, partition)
        partition = partition.stage_plan
    else:
        qmap = _resolve_link_quant(link_quant, graph, partition)
    wire: Dict[str, str] = {}  # producer -> wire dtype (one stream each)
    for (u, _v), dt in qmap.items():
        if wire.setdefault(u, dt) != dt:
            raise GraphExecutionError(
                f"conflicting link dtypes for producer {u!r}: one physical "
                f"stream leaves it, so all its cut edges must share a width"
            )
    if list(partition.order) != graph.topo_order():
        raise GraphExecutionError(
            "partition does not cover this graph (node order differs)"
        )
    if executed is None:
        executed = {}
    table = _build_table(
        impls=impls,
        plan=plan,
        overrides=overrides,
        graph=graph,
        executed=executed,
    )
    overridden = frozenset(overrides or ())
    imports, exports = _stage_io(graph, partition, out_name)

    stage_fns = []
    for s in range(partition.n_stages):

        def run_stage(
            sp,
            bnd,
            xin,
            nodes=partition.stage_nodes(s),
            out=tuple(sorted(exports[s])),
        ):
            values = dict(bnd)
            _run_nodes(
                graph,
                nodes,
                values,
                sp,
                table,
                x_input=xin,
                plan=plan,
                executed=executed,
                overridden=overridden,
                check=check,
                link_quant=qmap,
            )
            return {
                e: _link_encode(values[e], wire[e]) if e in wire else values[e]
                for e in out
            }

        stage_fns.append(jax.jit(run_stage) if jit else run_stage)

    pipeline = StagePipeline(
        partition=partition,
        stage_fns=stage_fns,
        imports=imports,
        exports=exports,
        out_name=out_name,
        link_quant_edges=qmap,
        devices=resolve_stage_devices(placement, partition.n_stages, partition),
    )
    if cache_key is not None:
        cache[cache_key] = (cache_refs, pipeline)
    return pipeline


class StagePipeline:
    """The compiled stages of a partition plus their boundary wiring.

    ``run_stage(s, params, boundary, x)`` executes one stage against a
    per-batch ``boundary`` dict (imported tensors in, exported tensors
    merged back in) — the serving engine calls this as micro-batches
    advance; ``staged_forward``'s returned callable is just the s-loop.

    With ``devices`` (a per-stage device tuple from
    ``resolve_stage_devices``) the pipeline is *placed*: stage params
    are moved to their stage's device once and kept resident, and every
    ``run_stage`` first moves the stage's imported boundary tensors
    there (``prefetch``), donating each source buffer on its last
    consuming stage.  Because the moved operands are committed, each
    stage's jitted fn computes on its own device — drivers that
    dispatch without blocking get genuine multi-device overlap.
    """

    def __init__(
        self,
        *,
        partition,
        stage_fns,
        imports,
        exports,
        out_name,
        link_quant_edges=None,
        devices=None,
    ):
        self.partition = partition
        self.stage_fns = stage_fns
        self.imports = imports
        self.exports = exports
        self.out_name = out_name
        # {(src, dst): wire dtype} of the quantized crossings ({} = off);
        # boundary values for encoded producers are wire payloads, not
        # activations — decode with ``decode_boundary`` before comparing.
        self.link_quant_edges = dict(link_quant_edges or {})
        self.devices = tuple(devices) if devices else None
        # imports only stage s consumes: their transfer may donate the
        # source buffer (double-buffering frees the producer-side copy)
        self._donate = []
        for s in range(len(imports)):
            later = set().union(*imports[s + 1 :]) if imports[s + 1 :] else set()
            self._donate.append({u for u in imports[s] if u not in later})
        self._placed_params: Dict[int, tuple] = {}
        self._observer = None

    @property
    def n_stages(self) -> int:
        return self.partition.n_stages

    def stage_params(self, s: int, params: Params) -> Params:
        nodes = self.partition.stage_nodes(s)
        return {n: params[n] for n in nodes if n in params}

    def stage_device(self, s: int):
        """The device stage ``s`` is placed on (None when unplaced)."""
        return None if self.devices is None else self.devices[s]

    def observe(self, hook) -> None:
        """Register ``hook(stage=, name=, nbytes=, dtype=, donated=)``,
        called on every placed cut transfer ``prefetch`` issues — the
        measured twin of the plan's priced ``StreamBuffer`` wire widths
        (the serving engine folds it into ``transfer_bytes{edge,dtype}``;
        see docs/observability.md).  Pass ``None`` to detach.  Attach
        only to a pipeline you own: pipelines served from a shared
        ``stage_functions`` cache are reused across engines."""
        self._observer = hook

    def keep_after(self) -> List[set]:
        """``keep_after()[s]``: the boundary keys still live once stage
        ``s`` has run — what later stages import, plus the graph output
        after the final stage.  Pipelining drivers (the serving engine,
        ``DevicePipeline``) prune everything else per batch."""
        keep: set = set()
        out: List[set] = [set() for _ in range(self.n_stages)]
        for s in range(self.n_stages - 1, -1, -1):
            if s == self.n_stages - 1:
                keep = {self.out_name}
            else:
                keep = keep | set(self.imports[s + 1])
            out[s] = set(keep)
        return out

    def _placed_stage_params(self, s: int, params: Params) -> Params:
        ent = self._placed_params.get(s)
        if ent is not None and ent[0] is params:
            return ent[1]
        sp = jax.device_put(self.stage_params(s, params), self.devices[s])
        self._placed_params[s] = (params, sp)
        return sp

    def prefetch(self, s: int, boundary: Dict[str, jax.Array]) -> None:
        """Move stage ``s``'s imports onto its device *now*.

        The double-buffered half of a crossing: issued right after the
        producing stage dispatches, the (async) copy overlaps other
        stages' compute, and ``run_stage(s, ...)`` later finds its
        operands already resident.  The moved value replaces the
        boundary entry; when no later stage imports the key the
        transfer donates the source buffer.  No-op when unplaced.
        """
        if self.devices is None:
            return
        dev = self.devices[s]
        for u in self.imports[s]:
            if u in boundary:
                v = boundary[u]
                if self._observer is not None:
                    self._observer(
                        stage=s,
                        name=u,
                        nbytes=int(v.nbytes),
                        dtype=str(v.dtype),
                        donated=(u in self._donate[s]),
                    )
                boundary[u] = jax.device_put(v, dev, donate=(u in self._donate[s]))

    def run_stage(
        self,
        s: int,
        params: Params,
        boundary: Dict[str, jax.Array],
        x: Optional[jax.Array] = None,
    ) -> Dict[str, jax.Array]:
        if self.devices is None:
            sp = self.stage_params(s, params)
        else:
            self.prefetch(s, boundary)
            sp = self._placed_stage_params(s, params)
            if s == 0 and x is not None:
                x = jax.device_put(x, self.devices[0])
        bnd_in = {u: boundary[u] for u in self.imports[s]}
        out = self.stage_fns[s](sp, bnd_in, x if s == 0 else None)
        boundary.update(out)
        return boundary

    def decode_boundary(
        self, boundary: Dict[str, jax.Array]
    ) -> Dict[str, jax.Array]:
        """The boundary dict with every wire payload decoded back into
        an activation (int8 dequantized, bf16 upcast) — what to compare
        against the monolithic reference when link quantization is on."""
        wire = {u: dt for (u, _v), dt in self.link_quant_edges.items()}
        return {
            name: _link_decode(v, wire[name]) if name in wire else v
            for name, v in boundary.items()
        }


def staged_forward(
    graph: LayerGraph,
    *,
    partition,
    impls: Optional[Dict[str, Impl]] = None,
    plan: Optional[Mapping[str, ImplPlan]] = None,
    overrides: Optional[Mapping[str, Impl]] = None,
    executed: Optional[Dict[str, Dict[str, int]]] = None,
    dtype=jnp.float32,
    check: bool = True,
    jit: bool = True,
    link_quant=None,
    placement=None,
    cache: Optional[dict] = None,
) -> Callable[[Params, jax.Array], Dict[str, jax.Array]]:
    """Compile the staged pipeline ONCE; returns ``fn(params, x)``.

    The returned callable threads the boundary activations through the
    per-stage functions (each wrapped in ``jax.jit`` exactly once, so
    repeated calls — a serving loop, a benchmark timing loop — hit the
    jit cache instead of retracing every stage per call) and returns
    the dict of every cut-crossing tensor plus the graph output, keyed
    by node name.  ``apply_staged`` is the one-shot convenience wrapper;
    ``stage_functions`` exposes the stages individually for the
    streaming serving engine's software pipeline.

    With ``link_quant`` (see ``stage_functions``) the wire payloads are
    decoded before the boundary is returned — the caller sees
    activations as quantized crossings actually delivered them.
    ``placement`` / ``cache`` thread through to ``stage_functions``
    (multi-device stage placement; compiled-pipeline memoization).
    """
    pipeline = stage_functions(
        graph,
        partition=partition,
        impls=impls,
        plan=plan,
        overrides=overrides,
        executed=executed,
        check=check,
        jit=jit,
        link_quant=link_quant,
        placement=placement,
        cache=cache,
    )

    def forward(params: Params, x: jax.Array) -> Dict[str, jax.Array]:
        x = x.astype(dtype)
        boundary: Dict[str, jax.Array] = {}
        for s in range(pipeline.n_stages):
            pipeline.run_stage(s, params, boundary, x if s == 0 else None)
        return pipeline.decode_boundary(boundary)

    return forward


def apply_staged(
    params: Params,
    x: jax.Array,
    graph: LayerGraph,
    *,
    partition,
    impls: Optional[Dict[str, Impl]] = None,
    plan: Optional[Mapping[str, ImplPlan]] = None,
    overrides: Optional[Mapping[str, Impl]] = None,
    executed: Optional[Dict[str, Dict[str, int]]] = None,
    dtype=jnp.float32,
    check: bool = True,
    jit: bool = True,
    check_monolithic: bool = False,
    link_quant=None,
    placement=None,
    cache: Optional[dict] = None,
) -> jax.Array:
    """Multi-chip forward pass: execute ``graph`` stage by stage.

    ``partition`` is a ``core.stage_partition.GraphStagePlan`` (or a
    ``core.graph.GraphPlan`` planned with ``n_stages=``, from which the
    stage plan is taken).  Each stage's subgraph is jitted *separately*
    (``jit=False`` keeps them eager — then the op sequence is identical
    to ``apply_graph`` and outputs are bit-exact); activations crossing
    a cut — including the skew-buffered shortcut tensors of joins whose
    branch lives in an upstream stage — are threaded across the stage
    boundaries exactly as the inter-chip stream buffers would carry
    them.  ``impls`` / ``plan`` / ``overrides`` / ``check`` behave as
    in ``apply_graph``; the per-node shape/MAC and executed-tile
    assertions run inside each stage's trace.

    This is the one-shot form: without ``cache`` it builds (and jits)
    the stage pipeline per call.  Pass ``cache`` (a dict the caller
    owns — ``registry.CNNApi`` does this automatically) to memoize the
    compiled pipeline across calls, or build it once yourself with
    ``staged_forward`` and reuse the returned callable — either way the
    per-stage jit cache amortizes.  ``placement`` places stage ``s`` on
    its own device (see ``stage_functions``).

    ``check_monolithic=True`` additionally runs the monolithic
    ``apply_graph`` on the same inputs and asserts every cut-crossing
    tensor (and the final output) matches it — the staged execution
    provably computes the same network.  With ``link_quant`` the
    monolithic reference applies the identical fake-quant on the mapped
    edges, so the contract holds for quantized crossings too.
    """
    out_name = _check_single_stream(graph)
    user_executed = executed is not None
    if executed is None:
        executed = {}
    forward = staged_forward(
        graph,
        partition=partition,
        impls=impls,
        plan=plan,
        overrides=overrides,
        executed=executed if user_executed else None,
        dtype=dtype,
        check=check,
        jit=jit,
        link_quant=link_quant,
        placement=placement,
        cache=cache,
    )
    boundary = forward(params, x)

    if check_monolithic:
        table = _build_table(
            impls=impls,
            plan=plan,
            overrides=overrides,
            graph=graph,
            executed=executed,
        )
        qmap = _resolve_link_quant(link_quant, graph, partition)
        mono: Dict[str, jax.Array] = {}
        _run_nodes(
            graph,
            graph.topo_order(),
            mono,
            params,
            table,
            x_input=x.astype(dtype),
            plan=plan,
            executed=executed,
            overridden=frozenset(overrides or ()),
            check=False,
            link_quant=qmap,
        )
        wire = {u: dt for (u, _v), dt in qmap.items()}
        for name, val in boundary.items():
            ref = mono[name]
            if name in wire:
                # staged boundary values for encoded producers are the
                # *delivered* (decoded) activations — round-trip the
                # reference through the same wire format before comparing
                ref = _link_decode(ref, wire[name])
            if not np.allclose(
                np.asarray(val), np.asarray(ref), rtol=1e-5, atol=1e-5
            ):
                raise GraphExecutionError(
                    f"staged output for {name!r} diverges from the "
                    f"monolithic apply_graph"
                )
    return boundary[out_name]


# ==========================================================================
# int8 simulated-quantization path (paper runs an 8-bit datapath)
# ==========================================================================


def quantize_params(params: Params, bits: int = 8):
    """Per-tensor symmetric int8 weights; returns (q_params, scales)."""
    qmax = 2 ** (bits - 1) - 1
    q, scales = {}, {}
    for name, p in params.items():
        s = jnp.maximum(jnp.max(jnp.abs(p["w"])), 1e-8) / qmax
        q[name] = {"w": jnp.round(p["w"] / s).astype(jnp.int8), "b": p["b"]}
        scales[name] = s
    return q, scales


def dequantize_params(q_params, scales, dtype=jnp.float32) -> Params:
    return {
        name: {"w": p["w"].astype(dtype) * scales[name], "b": p["b"]}
        for name, p in q_params.items()
    }


def apply_int8(
    q_params,
    scales,
    x: jax.Array,
    graph: LayerGraph,
    *,
    impls: Optional[Dict[str, Impl]] = None,
    plan: Optional[Mapping[str, ImplPlan]] = None,
    overrides: Optional[Mapping[str, Impl]] = None,
    partition=None,
    dtype=jnp.float32,
    check: bool = True,
    jit: bool = True,
) -> jax.Array:
    """Inference with int8 weights dequantized on the fly (sim of the
    FPGA's int8 datapath; activations stay float — activation quant is
    exercised in the kernels' int8 mode).  ``plan`` threads the same
    rate-matched per-node tiling as ``apply_graph``; ``overrides`` the
    same node-keyed impls; ``partition`` routes through the staged
    multi-chip executor (``apply_staged``) instead of the monolithic
    pass (``jit`` applies per stage there; it is ignored otherwise)."""
    deq = dequantize_params(q_params, scales, dtype)
    if partition is not None:
        return apply_staged(
            deq,
            x,
            graph,
            partition=partition,
            impls=impls,
            plan=plan,
            overrides=overrides,
            dtype=dtype,
            check=check,
            jit=jit,
        )
    return apply_graph(
        deq,
        x,
        graph,
        impls=impls,
        plan=plan,
        overrides=overrides,
        dtype=dtype,
        check=check,
    )
