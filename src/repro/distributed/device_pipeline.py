"""DevicePipeline: wall-clock multi-device staged CNN execution.

The tick-level serving engine (``serving/cnn_stream.py``) and the
discrete-event validator *model* pipeline overlap; this module is where
the repo finally *measures* it.  A ``DevicePipeline`` takes the compiled
per-stage functions of a stage partition (``models.cnn.stage_functions``
with ``placement=``), places each stage on a device
(``models.cnn.resolve_stage_devices``: one device per stage by default;
asking for more distinct devices than exist is an error, co-resident
stages only by request), and drives them with
the same software GPipe schedule ``distributed.pipeline_parallel``
builds inside ``shard_map``:

    for t in 0 .. M+S-2:           # M micro-batches, S stages
        for s in min(S-1, t) .. 0:  # deepest stage first
            m = t - s
            stage s computes micro-batch m

Stages are dispatched *without blocking*: JAX's async dispatch enqueues
each stage's jitted computation on its own device queue, so while stage
1 crunches micro-batch m, stage 0's kernel for micro-batch m+1 is
already running — genuine overlap on silicon, not just in the tick
model.  Cut-crossing boundary tensors move with donated, double-buffered
``jax.device_put`` transfers (``StagePipeline.prefetch``): the copy for
stage ``s+1`` is issued right after stage ``s`` dispatches, overlapping
other stages' compute, and the source buffer is donated on its last
consuming stage.  With quantized links (``link_quant``) the transfers
carry the int8 wire payloads, so inter-device traffic shrinks exactly as
the plan's ``StreamBuffer`` widths priced.

The steady-state bound is the same
``pipeline_parallel.microbatch_utilization`` the cost model uses:
utilization = M / (M + S - 1) — the fill/drain bubble amortizes as M
grows.  ``DevicePipeline.measure`` reports where a real host lands
against it: warmed-up wall-clock frames/sec for the overlapped schedule
vs a per-micro-batch blocking sequential pass over the *same* compiled
stages, per-stage busy seconds, and the overlap speedup
(``benchmarks/table10_wallclock.py`` is the harness; timing rows are
excluded from regression gating, structural rows are pinned).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.distributed.pipeline_parallel import microbatch_utilization
from repro.models import cnn
from repro.obs.trace import host_now, resolve_tracer


@dataclasses.dataclass(frozen=True)
class WallClockReport:
    """Measured wall-clock behaviour of one ``DevicePipeline.measure``.

    ``overlap_s``/``sequential_s`` are best-of-``repeats`` wall times
    for the whole batch; ``speedup = sequential_s / overlap_s`` (>1 on
    hosts with real parallel devices, ~1 on a single-device host where
    both schedules serialize onto one queue).  ``stage_busy_s[s]`` is
    stage ``s``'s serialized compute+transfer time (measured blocking,
    one stage at a time), ``stage_busy_frac[s]`` that time over the
    overlapped wall clock.  ``utilization_bound`` is the schedule's
    M/(M+S-1) ceiling — structural, pinned in regression baselines,
    while every measured field is excluded from gating (timing noise is
    not a regression).
    """

    frames: int                      # batch rows pushed per timed run
    microbatch: int                  # rows per micro-batch
    n_micro: int                     # M
    n_stages: int                    # S
    n_devices: int                   # distinct devices the stages landed on
    placement: Tuple[int, ...]       # device ordinal per stage
    utilization_bound: float         # M / (M + S - 1)
    overlap_s: float
    sequential_s: float
    fps_overlap: float
    fps_sequential: float
    speedup: float
    stage_busy_s: Tuple[float, ...]
    stage_busy_frac: Tuple[float, ...]


class DevicePipelineError(RuntimeError):
    pass


class DevicePipeline:
    """Drive a placed ``StagePipeline`` with the GPipe schedule.

    ``pipeline`` should come from ``models.cnn.stage_functions(...,
    placement=...)`` (or ``DevicePipeline.build``).  An unplaced
    pipeline is placed in-place via ``placement`` (default ``True``:
    the partition's recorded ordinals, else stage ``s`` on
    ``jax.devices()[s]``) — pass a pipeline you own, not one served from a
    shared memo cache, or build with ``placement=`` up front.

    ``run(x, microbatch=m)`` splits ``x`` into M = ceil(N/m)
    micro-batches, pumps them through the schedule, and returns the
    re-assembled logits (still async — block with ``np.asarray`` /
    ``jax.block_until_ready`` when timing).  Identical maths to
    ``staged_forward``: bit-exact with quantized links, allclose in
    fp32 (stage order never changes the per-node computation).
    """

    def __init__(self, pipeline, params, *, placement=True, tracer=None):
        if pipeline.devices is None:
            pipeline.devices = cnn.resolve_stage_devices(
                placement, pipeline.n_stages, pipeline.partition
            )
        if pipeline.devices is None:
            raise DevicePipelineError(
                "DevicePipeline needs a placed StagePipeline — build with "
                "stage_functions(..., placement=True) or pass placement="
            )
        self.pipeline = pipeline
        self.params = params
        self._keep = pipeline.keep_after()
        # opt-in obs.Tracer: host wall-clock spans around every
        # dispatch / cut transfer / block_until_ready, one pid per
        # device ordinal, one tid per stage (docs/observability.md).
        # None/False = off (no timing perturbation), True = fresh.
        self.tracer = resolve_tracer(tracer)

    @classmethod
    def build(
        cls,
        graph,
        params,
        *,
        partition,
        placement=True,
        tracer=None,
        **stage_kwargs,
    ):
        """One-call constructor: compile the per-stage functions with
        ``placement`` and wrap them.  ``stage_kwargs`` flow through to
        ``models.cnn.stage_functions`` (impls/plan/overrides/link_quant/
        jit/cache/...)."""
        pipeline = cnn.stage_functions(
            graph, partition=partition, placement=placement, **stage_kwargs
        )
        return cls(pipeline, params, tracer=tracer)

    # -- placement introspection ------------------------------------------

    @property
    def n_stages(self) -> int:
        return self.pipeline.n_stages

    def placement_ordinals(self) -> Tuple[int, ...]:
        """Device ordinal (index into ``jax.devices()``) per stage."""
        devs = jax.devices()
        return tuple(devs.index(d) for d in self.pipeline.devices)

    def n_devices(self) -> int:
        """Distinct devices the stages actually landed on."""
        return len(set(self.pipeline.devices))

    # -- execution ---------------------------------------------------------

    def _split(self, x, microbatch: Optional[int]):
        x = jnp.asarray(x)
        n = x.shape[0]
        mb = n if microbatch is None else int(microbatch)
        if mb < 1:
            raise DevicePipelineError(f"microbatch must be >= 1, got {mb}")
        return [x[i : i + mb] for i in range(0, n, mb)], mb

    def _schedule(self, splits) -> List[jax.Array]:
        """The GPipe loop: dispatch every (stage, micro-batch) cell
        without blocking, deepest stage first within each step so each
        device queue receives its next kernel before new work enters
        stage 0.  Returns the per-micro-batch logits (async)."""
        pipe, S, M = self.pipeline, self.pipeline.n_stages, len(splits)
        tr = self.tracer
        ords = self.placement_ordinals() if tr is not None else ()
        bnds: List[Dict[str, jax.Array]] = [{} for _ in range(M)]
        outs: List[Optional[jax.Array]] = [None] * M
        for t in range(M + S - 1):
            for s in range(min(S - 1, t), -1, -1):
                m = t - s
                if not 0 <= m < M:
                    continue
                if tr is not None:
                    t0 = host_now()
                pipe.run_stage(s, self.params, bnds[m], splits[m] if s == 0 else None)
                if tr is not None:
                    tr.span(
                        "dispatch",
                        t0,
                        host_now(),
                        pid=f"dev{ords[s]}",
                        tid=f"stage{s}",
                        clock="host",
                        micro=m,
                    )
                keep = self._keep[s]
                for k in list(bnds[m]):
                    if k not in keep:
                        del bnds[m][k]
                if s == S - 1:
                    outs[m] = bnds[m][pipe.out_name]
                else:
                    # double-buffer: start the cut crossing toward stage
                    # s+1 now, overlapping every other stage's compute
                    if tr is not None:
                        t0 = host_now()
                    pipe.prefetch(s + 1, bnds[m])
                    if tr is not None:
                        tr.span(
                            "transfer",
                            t0,
                            host_now(),
                            pid=f"dev{ords[s]}",
                            tid=f"stage{s}",
                            clock="host",
                            micro=m,
                        )
        return outs

    def run(self, x, *, microbatch: Optional[int] = None) -> jax.Array:
        splits, _ = self._split(x, microbatch)
        outs = self._schedule(splits)
        return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=0)

    def _run_sequential(self, splits) -> List[jax.Array]:
        """The no-overlap baseline: same compiled stages, same
        micro-batches, but each micro-batch is walked through all S
        stages and *blocked on* before the next is admitted — what
        ``staged_forward`` does per call.  Any wall-clock gap to
        ``_schedule`` is pipeline overlap, not compilation skew."""
        pipe, S = self.pipeline, self.pipeline.n_stages
        outs = []
        for xm in splits:
            bnd: Dict[str, jax.Array] = {}
            for s in range(S):
                pipe.run_stage(s, self.params, bnd, xm if s == 0 else None)
            out = bnd[pipe.out_name]
            jax.block_until_ready(out)
            outs.append(out)
        return outs

    def _stage_busy(self, splits) -> Tuple[float, ...]:
        """Serialized per-stage seconds: run one (stage, micro-batch)
        cell at a time, blocking around it — the busy time each device
        would spend if nothing overlapped."""
        pipe, S = self.pipeline, self.pipeline.n_stages
        busy = [0.0] * S
        for xm in splits:
            bnd: Dict[str, jax.Array] = {}
            for s in range(S):
                t0 = time.perf_counter()
                pipe.run_stage(s, self.params, bnd, xm if s == 0 else None)
                jax.block_until_ready({k: bnd[k] for k in pipe.exports[s]})
                busy[s] += time.perf_counter() - t0
        return tuple(busy)

    def measure(
        self,
        x,
        *,
        microbatch: Optional[int] = None,
        warmup: int = 1,
        repeats: int = 3,
    ) -> WallClockReport:
        """Warm up (compile + place), then time the overlapped schedule
        against the blocking sequential pass; best-of-``repeats`` each.
        Returns a ``WallClockReport`` — measured fields are advisory
        (excluded from regression gating), structural fields are pinned.
        """
        splits, mb = self._split(x, microbatch)
        frames = int(sum(s.shape[0] for s in splits))
        for _ in range(max(1, warmup)):
            jax.block_until_ready(self._schedule(splits))
            self._run_sequential(splits)

        def _overlap_once():
            outs = self._schedule(splits)
            if self.tracer is None:
                jax.block_until_ready(outs)
                return
            t0 = host_now()
            jax.block_until_ready(outs)
            self.tracer.span(
                "block_until_ready",
                t0,
                host_now(),
                pid="host",
                tid="measure",
                clock="host",
                frames=frames,
            )

        overlap_s = min(self._timed(_overlap_once) for _ in range(max(1, repeats)))
        sequential_s = min(
            self._timed(lambda: self._run_sequential(splits))
            for _ in range(max(1, repeats))
        )
        busy = self._stage_busy(splits)

        return WallClockReport(
            frames=frames,
            microbatch=mb,
            n_micro=len(splits),
            n_stages=self.n_stages,
            n_devices=self.n_devices(),
            placement=self.placement_ordinals(),
            utilization_bound=microbatch_utilization(len(splits), self.n_stages),
            overlap_s=overlap_s,
            sequential_s=sequential_s,
            fps_overlap=frames / overlap_s if overlap_s > 0 else float("inf"),
            fps_sequential=(
                frames / sequential_s if sequential_s > 0 else float("inf")
            ),
            speedup=sequential_s / overlap_s if overlap_s > 0 else float("inf"),
            stage_busy_s=busy,
            stage_busy_frac=tuple(
                b / overlap_s if overlap_s > 0 else 0.0 for b in busy
            ),
        )

    @staticmethod
    def _timed(fn) -> float:
        t0 = time.perf_counter()
        fn()
        return time.perf_counter() - t0


def device_placement_rows(
    n_stages: int, n_devices: int
) -> List[Tuple[str, int]]:
    """Structural (pinned) rows for the wall-clock benchmark: the
    round-robin ordinal of every stage on an ``n_devices`` host —
    pure arithmetic, identical on every machine."""
    from repro.core.stage_partition import round_robin_placement

    return [
        (f"stage{s}_dev", d)
        for s, d in enumerate(round_robin_placement(n_stages, n_devices))
    ]
