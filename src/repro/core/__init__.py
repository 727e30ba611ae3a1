"""Core of the reproduction: the paper's data-rate-aware DSE.

Public surface:
  rate          — data-rate algebra (exact fractions), LayerSpec, propagation
  graph         — DAG rate graph: branch/join propagation, skew-buffer
                  sizing, DAG-aware DSE (plan_graph), and the per-node
                  ImplPlan contract consumed by the kernel executor
  dse           — (j,h) design-space exploration, Eqs. (1)-(11), both schemes
  multipixel    — §II-E phase analysis: tap routing, stride pruning
  schedule      — discrete-event continuous-flow validation (chain + DAG)
  resource_model— analytical FPGA model reproducing Tables I & II,
                  plus DAG skew-FIFO terms (estimate_graph)
  tpu_tiles     — the TPU adaptation: (j,h) -> Pallas BlockSpec tiles,
                  uniform (select_tile) and rate-matched per-layer
                  (select_tile_for_impl)
  stage_partition — rate-aware pipeline-stage partitioning: chain DP
                  (TPU analogue) + DAG cuts (partition_graph) with
                  inter-chip stream buffers (stream_buffers)
  replicate     — Multi-CLP bottleneck replication: clone the hot node R
                  ways behind a round-robin splitter / order-preserving
                  merger (plan_graph(replicate=...))
  hlo_analysis  — roofline term extraction from compiled HLO
  hw_specs      — hardware constants (TPU specs keyed by device_kind +
                  xcvu37p)
"""

from .rate import (  # noqa: F401
    LayerSpec,
    RatePoint,
    divisors,
    fps,
    frame_cycles,
    propagate,
    propagate_chain,
)
from .dse import (  # noqa: F401
    NON_ARITH_KINDS,
    LayerImpl,
    best_rate,
    hj_set,
    pixel_phases,
    plan_network,
    plan_partitioned,
    select_impl,
    select_ours,
    select_ref11,
    surviving_phases,
)
from .stage_partition import (  # noqa: F401
    GraphStagePlan,
    StagePlan,
    StreamBuffer,
    allocate_chips,
    partition_graph,
    partition_min_bottleneck,
    plan_node_costs,
    stream_buffers,
)
from .graph import (  # noqa: F401
    GraphError,
    GraphPlan,
    ImplPlan,
    JoinBuffer,
    LayerGraph,
    NodeTiming,
    compute_timing,
    deal_buffers,
    join_buffers,
    plan_graph,
    propagate_graph,
)
from .replicate import (  # noqa: F401
    ReplicatedGraph,
    ReplicatedPlan,
    Replication,
    lane_multiplicity,
    plan_replicated,
    replicable_nodes,
    replicate_node,
    replicate_params,
    select_bottleneck,
)
from .tpu_tiles import TileChoice, select_tile, select_tile_for_impl  # noqa: F401
from .hw_specs import (  # noqa: F401
    TPU_SPECS,
    TPU_V5E,
    XCVU37P,
    FPGASpec,
    TPUSpec,
    target_spec,
    tpu_spec,
)
from .resource_model import (  # noqa: F401
    ResourceEstimate,
    estimate_graph,
    estimate_join_buffer,
    estimate_layer,
    estimate_network,
    estimate_stages,
    estimate_stream_buffer,
)
