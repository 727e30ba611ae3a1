"""ServeConfig: the one serving surface.

Every serving decision lives in one frozen dataclass with four
separated groups:

* **execution knobs** — how admitted micro-batches are computed
  (``microbatch``, ``kernel_plan``, ``dtype``, ``jit``, ``execute``,
  ``link_quant``, ``pipeline_cache``); where Pallas kernels run
  (compiled on a TPU, interpreted elsewhere) is the backend's choice,
  not a knob.  The rate-matched path is ``kernel_plan``: the caller
  lowers it once (``GraphPlan.kernel_plan(batch=microbatch)``) and
  reuses it across calls;
* **arrival source** — what traffic the run sees: a bare rate
  (frames/tick, the legacy constant process) or any
  ``serving.scenarios.ArrivalProcess`` (``arrival``), plus the run
  bound ``max_ticks``;
* **flush / SLA / overload policy** — ``flush_after_ticks`` (straggler
  bound on partial micro-batches) and ``overload`` (``None``,
  ``serving.overload.ShedPolicy``, or ``serving.overload.SwitchPolicy``);
* **observability** — ``trace`` / ``trace_pid`` / ``trace_chips``: the
  opt-in ``obs.Tracer`` hookup (off by default and event-identical when
  off; see ``docs/observability.md``).

``CNNStreamEngine(graph, params, plan, config)``, ``CNNApi.serve(...,
config=...)``, ``serve_frames(..., config=...)``, and
``FleetScheduler(pool, config=...)`` (with per-tenant configs via
``TenantWorkload.config``) take it and nothing else.
"""

from __future__ import annotations

import dataclasses
from fractions import Fraction
from typing import Any, Mapping, Optional


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Everything one serving run needs, in one frozen value.

    ``dtype=None`` resolves to the engine default (float32).
    ``arrival`` is a constant rate in frames/tick (``Fraction``/int) or
    an ``ArrivalProcess``.  ``kernel_plan`` must be pinned to
    ``microbatch`` when given (``GraphPlan.kernel_plan(batch=B)``).
    """

    # -- execution knobs ---------------------------------------------------
    microbatch: int = 1
    kernel_plan: Optional[Mapping[str, Any]] = None
    dtype: Any = None
    jit: bool = True
    # False = plan/validate only; True = run stages (host placement);
    # "devices" = one device per stage (the plan's recorded placement,
    # else stage s on jax.devices()[s]; too few devices raise) so the
    # engine's interleaved stage pumping overlaps on real silicon (see
    # distributed.device_pipeline for the wall-clock harness).
    execute: Any = True
    # Quantized cut crossings (models.cnn.stage_functions link_quant):
    # None = full-precision boundaries (the default), True = the plan's
    # link_dtype, or a dtype str / per-producer / per-edge mapping.
    link_quant: Any = None
    # Memo dict for compiled StagePipelines (models.cnn.stage_functions
    # cache=).  CNNApi.serve injects the per-family cache automatically;
    # standalone engines may share one dict across runs to skip
    # re-tracing every stage per call.
    pipeline_cache: Optional[dict] = None
    # -- observability (obs.trace / obs.metrics; docs/observability.md) ----
    # None/False = off (the default — event-identical, zero-overhead),
    # True = record into a fresh private obs.Tracer, or an obs.Tracer
    # instance to share one trace across engines (what FleetScheduler
    # does: every tenant writes into the fleet's tracer under its own
    # pid).  When on, the engine also keeps an obs.MetricsRegistry per
    # run (folded into ServeSummary.metrics).
    trace: Any = None
    # pid label this engine's trace events are recorded under;
    # FleetScheduler overrides it with the tenant name.
    trace_pid: str = "engine"
    # optional {stage: chip label} tags stamped onto stage spans
    # (FleetScheduler sets the pool assignment here).
    trace_chips: Optional[Mapping[int, str]] = None
    # -- arrival source ----------------------------------------------------
    arrival: Any = Fraction(1)
    max_ticks: int = 1_000_000
    # -- flush / SLA / overload policy ---------------------------------------
    flush_after_ticks: Optional[Fraction] = None
    overload: Optional[Any] = None

    def with_(self, **changes) -> "ServeConfig":
        """A copy with ``changes`` applied (frozen-friendly update)."""
        return dataclasses.replace(self, **changes)
