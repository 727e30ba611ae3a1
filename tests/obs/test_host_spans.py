"""Host-clock spans of the serving engine (serving/cnn_stream.py).

A served call records, on the engine's ``host`` track and the clock the
JAX profiler stamps (``obs.host_now``, integer ns): ``serve_frames``
around the call, ``plan`` / ``build`` around a plan-cache miss and the
engine's construction, and one ``ingest``, ``dispatch`` and ``fetch``
per micro-batch; counters ``plan_builds`` / ``pipeline_builds`` count the
cache misses, and ``batches_in_flight`` the micro-batches dispatched and
not yet collected at each dispatch.  A host-only tracer records those
and nothing of the tick domain.
"""
import time
from fractions import Fraction as F

import jax
import numpy as np
import pytest

from repro.models.registry import get_cnn_api
from repro.obs import TraceError, Tracer, host_now
from repro.serving import ServeConfig

FAMILIES = ("mobilenet_v2", "resnet18")
CHILDREN = ("plan", "build", "ingest", "dispatch", "fetch")


def _served(family):
    api = get_cnn_api(family)
    cfg = api.make_config(input_hw=(32, 32), num_classes=10)
    params = api.init(cfg, jax.random.PRNGKey(0))
    return api, cfg, params


def _serve(served, n_frames, tracer, seed=0):
    api, cfg, params = served
    frames = np.random.RandomState(seed).randn(n_frames, 32, 32, 3)
    return api.serve(params, frames.astype(np.float32), cfg,
                     input_rate=F(3), n_stages=1,
                     config=ServeConfig(microbatch=4, trace=tracer))


@pytest.fixture(scope="module", params=FAMILIES)
def served(request):
    return _served(request.param)


def test_host_only_tracer_records_no_tick_domain(served):
    tr = Tracer(clocks=("host",))
    out, rep = _serve(served, 10, tr)
    assert out.shape == (10, 10)
    assert tr.events and {e.clock for e in tr.events} == {"host"}
    assert rep.metrics is None and rep.summary().metrics is None
    assert not tr.meta  # no analytic model for the tick-domain auditor
    assert not tr.select("stage") and not tr.select("submit")
    with pytest.raises(TraceError, match="clock 'ticks'"):
        tr.instant("submit", F(0), pid="engine")


def test_engine_spans_nest_in_serve_frames(served):
    """Balanced spans, all inside their call's ``serve_frames``, with
    exactly one ingest, dispatch and fetch per micro-batch (10 frames at
    micro-batch 4: 3 batches, the last padded)."""
    tr = Tracer(clocks=("host",))
    t0 = host_now()
    for seed in range(2):
        _serve(served, 10, tr, seed)
    t1 = host_now()
    tr.check_balanced()
    calls = tr.spans("serve_frames", pid="engine", tid="host", clock="host")
    assert len(calls) == 2
    assert all(isinstance(s.start, int) for s in calls)
    assert t0 <= calls[0].start < calls[0].end <= calls[1].start < calls[1].end <= t1
    assert [s.arg("frames") for s in calls] == [10, 10]
    for call in calls:
        inside = [s for s in tr.spans(pid="engine", tid="host")
                  if s.name != "serve_frames"
                  and call.start <= s.start <= s.end <= call.end]
        for name in ("ingest", "dispatch", "fetch"):
            bids = sorted(s.arg("bid") for s in inside if s.name == name)
            assert bids == [0, 1, 2], name
        assert sorted(s.arg("frames") for s in inside if s.name == "fetch") == [2, 4, 4]
        assert sum(s.duration for s in inside) <= call.duration
        # outputs collected one batch behind: batch 1 is dispatched before
        # batch 0 is fetched, and the last two are fetched after the last
        # dispatch, so the counter reads 1, then 2
        order = [(s.name, s.arg("bid")) for s in sorted(inside, key=lambda s: s.start)
                 if s.name in ("dispatch", "fetch")]
        assert order == [("dispatch", 0), ("dispatch", 1), ("fetch", 0),
                         ("dispatch", 2), ("fetch", 1), ("fetch", 2)]
        series = tr.counter_series("batches_in_flight", pid="engine", tid="host")
        flight = [v for t, v in series if call.start <= t <= call.end]
        assert flight == [1, 2, 2]
    for s in tr.spans(pid="engine"):
        if s.name in CHILDREN:
            assert any(c.start <= s.start <= s.end <= c.end for c in calls), s


def test_pipeline_builds_count_cache_misses():
    """The first call plans and builds (one miss each); later calls hit
    the api's caches and count nothing."""
    served = _served("resnet18")
    tr = Tracer(clocks=("host",))
    _serve(served, 4, tr)
    assert [v for _, v in tr.counter_series("pipeline_builds", pid="engine")] == [1.0]
    assert [v for _, v in tr.counter_series("plan_builds", pid="engine")] == [1.0]
    assert [s.arg("hit") for s in tr.spans("build")] == [False]
    assert len(tr.spans("plan")) == 1
    for seed in (1, 2):
        _serve(served, 4, tr, seed)
    assert len(tr.counter_series("pipeline_builds")) == 1
    assert len(tr.counter_series("plan_builds")) == 1
    assert [s.arg("hit") for s in tr.spans("build")] == [False, True, True]
    assert len(tr.spans("plan")) == 1


def test_full_tracer_keeps_tick_domain_and_adds_host_spans(served):
    tr = Tracer()
    _, rep = _serve(served, 8, tr)
    assert rep.metrics is not None
    assert len(tr.spans("stage", clock="ticks")) == 2
    assert len(tr.spans("fetch", clock="host")) == 2
    assert [v for _, v in tr.counter_series("batches_in_flight")] == [1, 2]
    assert not tr.spans("exec")


def test_host_clock_is_time_ns():
    lo = time.time_ns()
    t = host_now()
    assert isinstance(t, int) and lo <= t <= time.time_ns()


def test_host_events_export_at_real_microseconds():
    tr = Tracer(clocks=("host",))
    tr.span("fetch", 1_792_000_000_123_456_789, 1_792_000_000_125_456_789,
            pid="engine", tid="host", clock="host", bid=3)
    rows = [r for r in tr.to_chrome()["traceEvents"] if r["ph"] in "BE"]
    assert [r["ts"] for r in rows] == pytest.approx(
        [1_792_000_000_123_456.789, 1_792_000_000_125_456.789])
    (sp,) = Tracer.from_chrome(tr.dumps()).spans("fetch")
    assert sp.duration == 2_000_000 and isinstance(sp.start, int)
    assert sp.arg("bid") == 3


def test_tracer_clocks_are_checked():
    with pytest.raises(TraceError):
        Tracer(clocks=())
    with pytest.raises(TraceError):
        Tracer(clocks=("wall",))
    assert Tracer().clocks == ("ticks", "host")


def test_fleet_wall_clock_spans_ingest_to_fetch():
    """``tenant_wall_s``: a tenant's first ingest start to its last fetch
    end, in seconds."""
    from repro.fleet import (
        Chip, FleetScheduler, Tenant, TenantWorkload, chip_pool, plan_pool)

    tenants = (Tenant("a", "resnet18", F(1, 4), input_hw=(16, 16),
                      num_classes=4),)
    pool = plan_pool(tenants, (Chip("big0", bram36=4096),) + chip_pool(2),
                     s_options=(1,))
    sched = FleetScheduler(pool, config=ServeConfig(execute=True, trace=True))
    sched.init_params("a", jax.random.PRNGKey(0))
    frames = np.random.default_rng(0).standard_normal((5, 16, 16, 3))
    rep = sched.serve([TenantWorkload("a", frames.astype(np.float32))])
    ingest = rep.trace.spans("ingest", pid="a", clock="host")
    fetch = rep.trace.spans("fetch", pid="a", clock="host")
    assert ingest and len(ingest) == len(fetch)
    want = (fetch[-1].end - ingest[0].start) * 1e-9
    assert rep.tenant_wall_s["a"] == pytest.approx(want)
    assert rep.measured_fps("a") == pytest.approx(5 / want)
