"""Streaming CNN serving engine (serving/cnn_stream.py).

Covers the acceptance surface of the request-level rate calculus:

* the engine's per-stage telemetry against the analytical model that
  ``core.schedule.simulate_graph`` validates at pixel granularity —
  measured occupancy == max node demand/capacity, zero stalls whenever
  the admitted rate <= BestRate;
* bounded queues (within the stream-buffer-derived caps) and admission
  throttling to exactly BestRate under overload;
* served outputs vs the monolithic ``apply_graph``: fp32 allclose /
  bit-exact with the same kernel plan, int8 exact, with frames tracked
  by request id across micro-batch boundaries (including the padded
  final partial batch).
"""
from fractions import Fraction as F

import jax
import numpy as np
import pytest

from repro.core.graph import plan_graph
from repro.core.schedule import simulate_graph
from repro.models import cnn
from repro.models.registry import get_cnn_api
from repro.serving import ServeConfig
from repro.serving.cnn_stream import (
    CNNStreamEngine,
    ServingError,
    best_rate_frames,
    queue_caps_batches,
    stage_rates,
)

FAMILIES = ("mobilenet_v2", "resnet18")
ALL_FAMILIES = ("mobilenet_v1", "mobilenet_v2", "resnet18", "resnet34")


def _setup(family, n_stages, rate=F(3), hw=32):
    api = get_cnn_api(family)
    cfg = api.make_config(input_hw=(hw, hw), num_classes=10)
    graph = cfg.graph()
    plan = plan_graph(graph, rate, n_stages=n_stages)
    return api, cfg, graph, plan


def _timing_run(plan, graph, *, n_frames, arrival, microbatch=1):
    eng = CNNStreamEngine(graph, None, plan, ServeConfig(
        microbatch=microbatch, execute=False, arrival=arrival))
    for _ in range(n_frames):
        eng.submit(None)
    return eng.run()


# ---------------------------------------------------------------------------
# analytics: stage rates, BestRate, queue caps
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("family", FAMILIES)
def test_stage_utilization_is_max_node_ratio(family):
    """A stage's request-level utilization is exactly the max over its
    nodes of demand/capacity — the DSE quantity simulate_graph measures."""
    _, _, graph, plan = _setup(family, n_stages=3)
    for sr in stage_rates(plan):
        want = max(
            plan.impls[n].demand / plan.impls[n].capacity for n in sr.nodes
        )
        assert sr.utilization == want


@pytest.mark.parametrize("family", FAMILIES)
def test_best_rate_is_inverse_bottleneck_utilization(family):
    """Eq. 10 lifted: BestRate (frames/tick) == 1 / max node utilization."""
    _, _, graph, plan = _setup(family, n_stages=3)
    worst = max(i.demand / i.capacity for i in plan.impls.values())
    assert best_rate_frames(plan) == 1 / worst


@pytest.mark.parametrize("n_stages", [1, 2, 3])
def test_queue_caps_are_double_buffer_plus_stream_bits(n_stages):
    """Inter-stage queues: 2 micro-batches (double buffering) plus the
    stream-buffer pixel bound converted to whole frames — which floors
    to 0 extra for real frame sizes."""
    _, _, graph, plan = _setup("resnet18", n_stages=n_stages)
    caps = queue_caps_batches(plan, microbatch=2)
    assert len(caps) == n_stages
    assert all(c >= 2 for c in caps)
    # cut FIFOs hold pixels, not frames: far below one frame per cut
    for s in range(1, n_stages):
        assert caps[s] == 2


# ---------------------------------------------------------------------------
# telemetry vs the analytical bounds (simulate_graph cross-check)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("family", FAMILIES)
def test_occupancy_matches_simulate_graph(family):
    """The engine's measured stage occupancy at the plan rate equals the
    analytical bound — the same per-node utilization simulate_graph
    measures at pixel granularity (zero stalls in both models)."""
    _, _, graph, plan = _setup(family, n_stages=3)
    sim = simulate_graph(plan, n_pixels=256)
    assert sim.stall_free
    assert sim.within_bounds

    rep = _timing_run(plan, graph, n_frames=64, arrival=F(1))
    assert rep.stall_free
    for sr, stage_rep in zip(stage_rates(plan), rep.stages):
        # engine (request level) vs analytic bound: tight — the model is
        # exact up to the finite-run tail
        assert stage_rep.measured_occupancy == pytest.approx(
            float(stage_rep.analytic_occupancy), abs=0.02
        )
        # analytic bound vs simulate_graph's measured per-node util
        # (pixel level, edge effects at the tail => looser tolerance)
        sim_util = max(sim.traces[n].util for n in sr.nodes)
        assert float(sr.utilization) == pytest.approx(
            sim_util, rel=0.15, abs=0.05
        )


@pytest.mark.parametrize("family", ALL_FAMILIES)
@pytest.mark.parametrize("n_stages", [1, 2, 3])
def test_zero_stalls_at_or_below_best_rate(family, n_stages):
    """Acceptance: zero stalls and bounded queues for every family at
    S in {1, 2, 3} whenever the admitted rate <= BestRate."""
    _, _, graph, plan = _setup(family, n_stages=n_stages)
    br = best_rate_frames(plan)
    for arrival in (F(1, 2), F(1), br):
        rep = _timing_run(plan, graph, n_frames=32, arrival=arrival,
                          microbatch=2)
        assert rep.admitted_rate == min(arrival, br)
        assert rep.stall_free, (family, n_stages, arrival)
        assert rep.within_queue_bounds
        assert rep.completed == 32


@pytest.mark.parametrize("family", FAMILIES)
def test_backpressure_above_best_rate(family):
    """Above BestRate the engine admits at exactly BestRate: queues stay
    within their caps, the bottleneck saturates, and the excess waits in
    the request queue outside the pipeline."""
    _, _, graph, plan = _setup(family, n_stages=3)
    br = best_rate_frames(plan)
    rep = _timing_run(plan, graph, n_frames=48, arrival=2 * br,
                      microbatch=2)
    assert rep.admitted_rate == br
    assert rep.completed == 48
    assert rep.within_queue_bounds  # stable bounded queues: the claim
    assert rep.request_queue_peak > 0  # overload parked outside
    bott = rep.stages[rep.bottleneck_stage]
    assert bott.measured_occupancy == pytest.approx(1.0, abs=0.02)
    assert bott.stall_cycles == 0  # the bottleneck itself never starves
    # served no faster than BestRate (finite-run drain makes it slower)
    assert rep.throughput <= br


def test_tick_telemetry_series():
    """Per-tick occupancy/queue-depth traces: occupancy in [0, 1] and
    ~1 at the bottleneck mid-run; queue depths never exceed the caps."""
    _, _, graph, plan = _setup("resnet18", n_stages=2)
    rep = _timing_run(plan, graph, n_frames=32, arrival=F(1))
    for s, stage_rep in enumerate(rep.stages):
        occ = rep.tick_occupancy(s)
        assert all(0.0 <= v <= 1.0 + 1e-9 for v in occ)
        depths = rep.tick_queue_depth(s)
        assert max(depths) <= stage_rep.queue_cap_batches
    bott = rep.bottleneck_stage
    mid = rep.tick_occupancy(bott)[2:-2]
    assert all(v == pytest.approx(1.0) for v in mid)


# ---------------------------------------------------------------------------
# served outputs vs apply_graph (rid-tracked across micro-batches)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("family", FAMILIES)
def test_served_outputs_fp32_allclose(family):
    """Frames served through the pipelined engine (jitted stages, frames
    spread across micro-batches incl. a padded partial batch, admission
    above BestRate so queues/backpressure engage) match the monolithic
    apply_graph per request id."""
    api, cfg, graph, plan = _setup(family, n_stages=2)
    params = api.init(cfg, jax.random.key(0))
    frames = np.asarray(jax.random.normal(jax.random.key(1), (5, 32, 32, 3)))
    eng = CNNStreamEngine(graph, params, plan, ServeConfig(
        microbatch=2, dtype=cfg.dtype, arrival=2 * best_rate_frames(plan)))
    eng.submit_all(frames)
    rep = eng.run()
    assert rep.completed == 5
    out = eng.outputs()
    ref = np.asarray(api.apply(params, frames, cfg))
    assert np.allclose(out, ref, rtol=1e-5, atol=1e-5)


def test_served_outputs_bit_exact_with_pinned_plan():
    """With the batch-pinned rate-matched kernel plan, serving is
    bit-exact vs apply_graph(plan=...) on the same micro-batches: the
    engine runs the *same* kernels with the *same* tiles."""
    api, cfg, graph, plan = _setup("resnet18", n_stages=2)
    params = api.init(cfg, jax.random.key(0))
    frames = np.asarray(jax.random.normal(jax.random.key(1), (4, 32, 32, 3)))
    kp = plan.kernel_plan(batch=2)
    eng = CNNStreamEngine(graph, params, plan, ServeConfig(
        microbatch=2, kernel_plan=kp, dtype=cfg.dtype, arrival=F(1)))
    eng.submit_all(frames)
    eng.run()
    out = eng.outputs()
    ref = np.concatenate([
        np.asarray(api.apply(params, frames[i:i + 2], cfg, plan=kp))
        for i in range(0, 4, 2)
    ])
    assert np.array_equal(out, ref)


@pytest.mark.parametrize("family", FAMILIES)
def test_served_int8_bit_exact(family):
    """The int8 datapath served through the engine (eager stages so the
    op sequence is identical) is bit-exact vs apply_int8 on the same
    micro-batches."""
    api, cfg, graph, plan = _setup(family, n_stages=2)
    params = api.init(cfg, jax.random.key(0))
    frames = np.asarray(jax.random.normal(jax.random.key(1), (4, 32, 32, 3)))
    q, s = api.quantize(params)
    deq = cnn.dequantize_params(q, s, cfg.dtype)
    eng = CNNStreamEngine(graph, deq, plan, ServeConfig(
        microbatch=2, dtype=cfg.dtype, jit=False,
        arrival=2 * best_rate_frames(plan)))
    eng.submit_all(frames)
    eng.run()
    out = eng.outputs()
    ref = np.concatenate([
        np.asarray(api.apply_int8(q, s, frames[i:i + 2], cfg))
        for i in range(0, 4, 2)
    ])
    assert np.array_equal(out, ref)


def test_rid_tracking_under_out_of_order_submission():
    """Outputs map to their requests even when rids are submitted out of
    order: frame content is tied to rid, not to arrival position."""
    api, cfg, graph, plan = _setup("mobilenet_v2", n_stages=2)
    params = api.init(cfg, jax.random.key(0))
    frames = np.asarray(jax.random.normal(jax.random.key(1), (4, 32, 32, 3)))
    eng = CNNStreamEngine(graph, params, plan, ServeConfig(
        microbatch=3, dtype=cfg.dtype, arrival=F(1)))
    order = [2, 0, 3, 1]
    for rid in order:
        eng.submit(frames[rid], rid=rid)
    eng.run()
    out = eng.outputs()  # stacked in rid order
    ref = np.asarray(api.apply(params, frames, cfg))
    assert np.allclose(out, ref, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# guard rails
# ---------------------------------------------------------------------------

def test_requires_stage_partition():
    _, cfg, graph, _ = _setup("mobilenet_v2", n_stages=1)
    unstaged = plan_graph(graph, F(3))  # no n_stages
    with pytest.raises(ServingError, match="stage partition"):
        CNNStreamEngine(graph, None, unstaged, ServeConfig(execute=False))


def test_rejects_mismatched_pin():
    _, cfg, graph, plan = _setup("mobilenet_v2", n_stages=2)
    kp = plan.kernel_plan(batch=4)
    with pytest.raises(ServingError, match="pinned to batch"):
        CNNStreamEngine(graph, None, plan, ServeConfig(
            microbatch=2, kernel_plan=kp, execute=False))


def test_rejects_empty_run():
    _, cfg, graph, plan = _setup("mobilenet_v2", n_stages=2)
    eng = CNNStreamEngine(graph, None, plan, ServeConfig(execute=False))
    with pytest.raises(ServingError, match="no frames"):
        eng.run()


def test_lm_engine_routes_cnn_configs_here():
    """The token-stream Engine names this engine when handed a CNN
    config (which carries no .family — the structural check must fire
    before any attribute access)."""
    from repro.serving.engine import Engine

    cfg = get_cnn_api("resnet18").make_config(input_hw=(32, 32),
                                              num_classes=10)
    with pytest.raises(ValueError, match="CNNStreamEngine"):
        Engine(cfg, None)


def test_timing_only_has_no_outputs():
    _, cfg, graph, plan = _setup("mobilenet_v2", n_stages=2)
    eng = CNNStreamEngine(graph, None, plan, ServeConfig(execute=False))
    eng.submit(None)
    eng.run()
    with pytest.raises(ServingError, match="execute=False"):
        eng.outputs()


# ---------------------------------------------------------------------------
# straggler flush (flush_after_ticks)
# ---------------------------------------------------------------------------

def test_flush_after_ticks_bounds_straggler_latency():
    """At arrival rates far below the micro-batch fill rate, a partial
    batch used to wait for the whole stream; the flush knob bounds the
    wait to ``flush_after_ticks`` ticks per straggler."""
    _, cfg, graph, plan = _setup("mobilenet_v2", n_stages=2)
    mb, n, arrival = 4, 6, F(1, 8)  # one frame every 8 ticks

    def run(flush):
        eng = CNNStreamEngine(graph, None, plan, ServeConfig(
            microbatch=mb, execute=False, arrival=arrival,
            flush_after_ticks=flush))
        for _ in range(n):
            eng.submit(None)
        return eng.run()

    held = run(None)
    bounded = run(F(2))
    # without the knob the first frame waits for 3 more arrivals
    # (3 x 8 ticks) before its batch forms; with it, <= 2 ticks + service
    assert held.p99_latency() > 3 * 8
    assert bounded.p99_latency() < 8
    assert bounded.stall_free and bounded.within_queue_bounds
    assert bounded.completed == n
    # every frame still served exactly once, in more (smaller) batches
    assert bounded.completed == held.completed == n
    assert bounded.stages[0].batches_served > held.stages[0].batches_served


def test_flush_none_is_event_identical_to_legacy_run():
    """flush_after_ticks=None must not perturb the event sequence the
    table6 baselines pin (the steppable refactor is behavior-neutral)."""
    _, cfg, graph, plan = _setup("resnet18", n_stages=3)

    def run(**kw):
        eng = CNNStreamEngine(graph, None, plan, ServeConfig(
            microbatch=4, execute=False, arrival=F(1, 3), **kw))
        for _ in range(12):
            eng.submit(None)
        return eng.run()

    a, b = run(), run(flush_after_ticks=None)
    assert a.makespan_ticks == b.makespan_ticks
    assert a.latency_ticks == b.latency_ticks
    assert a.queue_events == b.queue_events


def test_flush_zero_serves_singleton_batches():
    _, cfg, graph, plan = _setup("mobilenet_v2", n_stages=2)
    eng = CNNStreamEngine(graph, None, plan, ServeConfig(
        microbatch=4, execute=False, arrival=F(1, 4),
        flush_after_ticks=F(0)))
    for _ in range(5):
        eng.submit(None)
    rep = eng.run()
    assert rep.stages[0].batches_served == 5  # nothing ever waits
    assert rep.completed == 5


def test_flush_rejects_negative():
    _, cfg, graph, plan = _setup("mobilenet_v2", n_stages=2)
    eng = CNNStreamEngine(graph, None, plan, ServeConfig(
        execute=False, flush_after_ticks=F(-1)))
    eng.submit(None)
    with pytest.raises(ServingError, match="flush_after_ticks"):
        eng.run()
