"""Pipeline parallelism with rate-aware stage balance — the paper's
continuous-flow constraint driving a multi-chip schedule.

Stages are assigned by ``core.stage_partition`` (min-bottleneck DP =
BestRate for stages).  Execution is the classic JAX circular-pipeline
pattern: shard_map over a 'stage' axis, microbatches streamed with
``jax.lax.ppermute`` moving activations stage->stage.  With M
microbatches and S stages, utilization is M/(M+S-1) — the pipeline-level
twin of the paper's j/h >= r utilization bound, asserted in tests.

This module implements the schedule for a homogeneous stack of layer
blocks (each stage runs `block_fn` over its parameter slice).  It is
used by examples/pipeline_demo.py (a 4-device CPU mesh via
``--xla_force_host_platform_device_count``) and tested on a CPU mesh in
tests/distributed/test_substrate.py (``pipeline_forward`` vs the
unpipelined stack, plus the utilization math).  The same schedule and
``microbatch_utilization`` bound drive the *wall-clock* executor for
staged CNNs in ``distributed.device_pipeline`` — there the stages are
heterogeneous subgraphs placed per device rather than a homogeneous
block stack sharded over a mesh axis.  The 40-cell dry-run uses DP x TP
(mesh (data, model)) as its baseline distribution, with PP as the
documented scale-out axis for >16k-chip fleets.
"""
from __future__ import annotations

from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.stage_partition import StagePlan, partition_blocks


def microbatch_utilization(n_micro: int, n_stages: int) -> float:
    """GPipe bubble math: busy fraction of each stage."""
    return n_micro / (n_micro + n_stages - 1)


def pipeline_forward(
    block_fn: Callable[[Any, jax.Array], jax.Array],
    stage_params: Any,  # leaves [S, layers_per_stage, ...]
    x_micro: jax.Array,  # [M, mb, ...] microbatched input
    mesh: Mesh,
    *,
    stage_axis: str = "stage",
) -> jax.Array:
    """Run M microbatches through S pipeline stages on ``mesh``.

    ``block_fn(params_slice, x)`` applies one stage's layers.  Returns the
    final-stage outputs re-assembled as [M, mb, ...].

    Implementation: circular pipeline over T = M + S - 1 ticks.  Each
    stage holds a buffer; every tick it (a) ingests (stage 0 pulls the
    next microbatch; others receive the ppermute'd activation), (b) runs
    its block, (c) forwards.  Outputs exit from the last stage.
    """
    n_stages = mesh.shape[stage_axis]
    m = x_micro.shape[0]
    ticks = m + n_stages - 1

    def per_stage(params_s, x_all):
        # params_s: [1, layers_per_stage, ...] slice for this stage
        # x_all: full [M, mb, ...] (stage 0 reads it; others ignore)
        params_s = jax.tree.map(lambda a: a[0], params_s)
        stage_id = jax.lax.axis_index(stage_axis)
        mb_shape = x_all.shape[1:]
        # carries are stage-varying (each stage holds different values):
        # annotate for shard_map's vma type system.
        buf = jax.lax.pcast(
            jnp.zeros(mb_shape, x_all.dtype), (stage_axis,), to="varying"
        )
        outs = jax.lax.pcast(
            jnp.zeros((m,) + mb_shape, x_all.dtype), (stage_axis,), to="varying"
        )

        def tick(carry, t):
            buf, outs = carry
            # stage 0 ingests microbatch t (if any remain)
            take = jnp.clip(t, 0, m - 1)
            fresh = jax.lax.dynamic_index_in_dim(x_all, take, 0, keepdims=False)
            buf = jnp.where(stage_id == 0, jnp.where(t < m, fresh, buf), buf)
            # compute
            y = block_fn(params_s, buf)
            # last stage banks its result for microbatch t - (S-1)
            out_idx = jnp.clip(t - (n_stages - 1), 0, m - 1)
            bank = (stage_id == n_stages - 1) & (t >= n_stages - 1)
            outs_upd = jax.lax.dynamic_update_index_in_dim(outs, y, out_idx, 0)
            outs = jnp.where(bank, outs_upd, outs)
            # forward activations around the ring
            y_next = jax.lax.ppermute(
                y, stage_axis, [(i, (i + 1) % n_stages) for i in range(n_stages)]
            )
            return (y_next, outs), None

        (_, outs), _ = jax.lax.scan(tick, (buf, outs), jnp.arange(ticks))
        # only the last stage's banked outputs are real; psum-select them
        outs = jnp.where(stage_id == n_stages - 1, outs, 0)
        return jax.lax.psum(outs, stage_axis)

    fn = jax.shard_map(
        per_stage,
        mesh=mesh,
        in_specs=(P(stage_axis), P()),
        out_specs=P(),
    )
    return fn(stage_params, x_micro)


def plan_stages_for_layers(
    costs: Sequence[float], n_stages: int, scan_block: int = 1
) -> StagePlan:
    """Rate-aware stage boundaries (divisibility-constrained DP)."""
    return partition_blocks(list(costs), n_stages, scan_block)


def stack_stage_params(params_layers: Any, plan: StagePlan) -> Any:
    """Reshape [L, ...] stacked layer params into [S, L/S, ...] when the
    plan is uniform; uneven plans pad to the bottleneck stage size (the
    padding layers are identity — weights zeroed)."""
    bounds = plan.boundaries
    sizes = [bounds[i + 1] - bounds[i] for i in range(len(bounds) - 1)]
    s_max = max(sizes)

    def per_leaf(leaf):
        pieces = []
        for i, size in enumerate(sizes):
            sl = leaf[bounds[i]:bounds[i + 1]]
            if size < s_max:
                pad = jnp.zeros((s_max - size,) + leaf.shape[1:], leaf.dtype)
                sl = jnp.concatenate([sl, pad], 0)
            pieces.append(sl)
        return jnp.stack(pieces)  # [S, s_max, ...]

    return jax.tree.map(per_leaf, params_layers)
