"""CNN stream serving.

``CNNStreamEngine`` streams CNN frame pipelines (the CNN registry
families) with BestRate admission (Eq. 9: admit only into free
capacity), micro-batching to the planned kernel tiles, and bounded
inter-stage queues.  The one front door is
``registry.CNNApi.serve`` -> ``serve_frames`` -> ``CNNStreamEngine``.

The engine is configured by one frozen ``ServeConfig`` (execution
knobs + arrival source + flush/SLA/overload policy), its only serving
surface.  Traffic shapes
come from ``serving.scenarios`` (constant / bursty / diurnal /
adversarial — seeded, deterministic, exact-rational); overload behavior
from ``serving.overload`` (``ShedPolicy`` SLA shedding, ``SwitchPolicy``
online plan switching over a ``PlanLadder``); rendered telemetry from
``serving.telemetry.ServeSummary``, the schema ``ServeReport`` and
``fleet.FleetReport`` share.

The token-stream engine of the language-model families is
``serving.engine.Engine``; this package does not import it.
"""

from repro.serving.cnn_stream import (
    CNNStreamEngine,
    FrameRequest,
    ServeReport,
    ServingError,
    StageReport,
    serve_frames,
)
from repro.serving.config import ServeConfig
from repro.serving.overload import (
    LadderRung,
    OverloadError,
    PlanLadder,
    ShedPolicy,
    SwitchPolicy,
)
from repro.serving.scenarios import (
    ArrivalProcess,
    Bursty,
    Constant,
    Diurnal,
    ScenarioError,
    adversarial,
    bursty,
    constant,
    diurnal,
)
from repro.serving.telemetry import ServeSummary

__all__ = [
    "ArrivalProcess",
    "Bursty",
    "CNNStreamEngine",
    "Constant",
    "Diurnal",
    "FrameRequest",
    "LadderRung",
    "OverloadError",
    "PlanLadder",
    "ScenarioError",
    "ServeConfig",
    "ServeReport",
    "ServeSummary",
    "ServingError",
    "ShedPolicy",
    "StageReport",
    "SwitchPolicy",
    "adversarial",
    "bursty",
    "constant",
    "diurnal",
    "serve_frames",
]
