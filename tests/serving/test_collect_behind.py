"""Outputs collected one micro-batch behind the dispatch
(serving/cnn_stream.py).

The engine waits on batch k's logits only when the last stage of batch
k+1 has been dispatched behind it and batch k+2's is next, so the device
always has the next program queued while the host copies logits back:

* the host spans show batch k+1's ``dispatch`` before batch k's
  ``fetch``, and batch k's ``fetch`` before batch k+2's ``ingest``;
* the ``batches_in_flight`` counter (micro-batches dispatched and not
  yet collected) never exceeds 2 and reaches 2;
* outputs stay bit-exact to ``apply_graph`` on the same micro-batches
  and land in request order: a partial last batch, SLA shedding, a
  ``SwitchPolicy`` ladder, two tenants through ``FleetScheduler``;
* every output is on the host when ``finish`` returns, and the report
  is the same with and without a tracer.
"""
from fractions import Fraction as F

import jax
import numpy as np
import pytest

from repro.core.graph import plan_graph
from repro.core.replicate import replicate_params
from repro.models import cnn
from repro.models.registry import get_cnn_api
from repro.obs import Tracer
from repro.serving import ServeConfig
from repro.serving.cnn_stream import CNNStreamEngine, best_rate_frames
from repro.serving.overload import PlanLadder, ShedPolicy, SwitchPolicy
from repro.serving.scenarios import adversarial, bursty

HW = 16


def _model(family, hw=HW, num_classes=4):
    api = get_cnn_api(family)
    cfg = api.make_config(input_hw=(hw, hw), num_classes=num_classes)
    return api, cfg, api.init(cfg, jax.random.key(0))


def _frames(n, hw=HW, seed=1):
    return np.asarray(jax.random.normal(jax.random.key(seed), (n, hw, hw, 3)))


def _served_batches(tracer, pid="engine"):
    """(rung, rids) of every micro-batch, from the tick trace's stage-0
    spans."""
    return [(sp.arg("rung"), list(sp.arg("rids")))
            for sp in tracer.spans("stage", pid=pid, tid="stage0")]


def _batches_of(n, mb):
    """Consecutive micro-batches of ``mb`` request ids on rung 0."""
    return [(0, list(range(i, min(i + mb, n)))) for i in range(0, n, mb)]


def _reference(batches, rungs, frames, microbatch, dtype):
    """Each micro-batch through ``apply_graph`` with its rung's graph,
    params and kernel plan, zero-padded as the engine pads it."""
    out = {}
    for rung_idx, rids in batches:
        graph, params, kp = rungs[rung_idx]
        x = frames[rids]
        pad = microbatch - len(rids)
        if pad:
            x = np.concatenate([x, np.zeros((pad,) + x.shape[1:], x.dtype)])
        y = np.asarray(cnn.apply_graph(params, x, graph, plan=kp, dtype=dtype))
        out.update(zip(rids, y[:len(rids)]))
    return out


def _engine_rungs(eng):
    return [(r.graph, r.params, r.kernel_plan) for r in eng._rungs]


def _host_order(tracer, pid="engine"):
    spans = [s for s in tracer.spans(pid=pid, tid="host", clock="host")
             if s.name in ("ingest", "dispatch", "fetch")]
    return sorted(spans, key=lambda s: s.start)


def _in_flight(tracer, pid="engine"):
    return [v for _, v in tracer.counter_series("batches_in_flight", pid=pid)]


@pytest.fixture(scope="module")
def pinned_run():
    """resnet18 at S=1 with the batch-pinned kernel plan: 7 frames at
    micro-batch 2, so four batches, the last one padded."""
    api, cfg, params = _model("resnet18")
    plan = plan_graph(cfg.graph(), F(3), n_stages=1)
    kp = plan.kernel_plan(batch=2)
    frames = _frames(7)
    tr = Tracer()
    eng = CNNStreamEngine(cfg.graph(), params, plan,
                          ServeConfig(microbatch=2, kernel_plan=kp, trace=tr))
    eng.submit_all(frames)
    rep = eng.run()
    return eng, rep, tr, frames, cfg


def test_dispatch_runs_one_batch_ahead_of_fetch(pinned_run):
    eng, rep, tr, _, _ = pinned_run
    assert rep.completed == 7
    order = _host_order(tr)
    assert [(s.name, s.arg("bid")) for s in order] == [
        ("ingest", 0), ("dispatch", 0),
        ("ingest", 1), ("dispatch", 1),
        ("fetch", 0), ("ingest", 2), ("dispatch", 2),
        ("fetch", 1), ("ingest", 3), ("dispatch", 3),
        ("fetch", 2), ("fetch", 3),
    ]
    first = {(s.name, s.arg("bid")): s for s in order}
    for k in range(3):
        assert first["dispatch", k + 1].end <= first["fetch", k].start
    for k in range(2):
        assert first["fetch", k].end <= first["ingest", k + 2].start
    assert [first["fetch", k].arg("frames") for k in range(4)] == [2, 2, 2, 1]


def test_batches_in_flight_reaches_two(pinned_run):
    _, _, tr, _, _ = pinned_run
    assert _in_flight(tr) == [1, 2, 2, 2]
    (track,) = {(e.tid, e.clock) for e in tr.select("batches_in_flight")}
    assert track == ("host", "host")


def test_pinned_outputs_bit_exact_in_request_order(pinned_run):
    eng, _, tr, frames, cfg = pinned_run
    batches = _served_batches(tr)
    assert [rids for _, rids in batches] == [[0, 1], [2, 3], [4, 5], [6]]
    ref = _reference(batches, _engine_rungs(eng), frames, 2, cfg.dtype)
    out = eng.outputs()
    assert out.shape == (7, 4)
    assert np.array_equal(out, np.stack([ref[r] for r in range(7)]))


@pytest.mark.parametrize("n_stages", [1, 2])
def test_finish_collects_every_output(n_stages):
    """Once the tick model has drained, the last two batches' outputs are
    still on the device; ``finish`` collects them, in request order."""
    api, cfg, params = _model("mobilenet_v2")
    plan = plan_graph(cfg.graph(), F(3), n_stages=n_stages)
    frames = _frames(9)
    tr = Tracer(clocks=("host",))
    eng = CNNStreamEngine(cfg.graph(), params, plan,
                          ServeConfig(microbatch=2, jit=False, trace=tr))
    eng.submit_all(frames)
    rt = eng.begin()
    while True:
        eng.advance(rt.t)
        if eng.finished:
            break
        rt.t = eng.next_event(rt.t)
    waiting = [r.rid for r in eng._requests if r.out is None]
    assert waiting == [6, 7, 8]  # batches 3 and 4 (the padded one)
    assert len(tr.spans("fetch")) == 3
    rep = eng.finish()
    assert not rt.unfetched
    assert all(r.out is not None for r in eng._requests)
    assert rep.completed == 9 and len(tr.spans("fetch")) == 5
    assert max(_in_flight(tr)) == 2
    ref = _reference(_batches_of(9, 2), [(cfg.graph(), params, None)],
                     frames, 2, cfg.dtype)
    assert np.array_equal(eng.outputs(), np.stack([ref[r] for r in range(9)]))


@pytest.mark.parametrize("n_stages", [1, 2])
def test_report_is_the_same_with_and_without_tracer(n_stages):
    api, cfg, params = _model("mobilenet_v2")
    plan = plan_graph(cfg.graph(), F(3), n_stages=n_stages)
    frames = _frames(9)
    reps, outs = [], []
    for trace in (None, Tracer()):
        eng = CNNStreamEngine(
            cfg.graph(), params, plan,
            ServeConfig(microbatch=2, jit=False, trace=trace,
                        arrival=2 * best_rate_frames(plan)))
        eng.submit_all(frames)
        reps.append(eng.run())
        outs.append(eng.outputs())
    off, on = reps
    assert off.summary().line() == on.summary().line()
    assert off.summary().to_rows() == on.summary().to_rows()
    assert off.latency_ticks == on.latency_ticks
    assert off.service_latency_ticks == on.service_latency_ticks
    assert off.queue_events == on.queue_events
    assert [(s.stage, s.busy_cycles, s.stall_cycles, s.batches_served)
            for s in off.stages] == [
        (s.stage, s.busy_cycles, s.stall_cycles, s.batches_served)
        for s in on.stages]
    assert np.array_equal(outs[0], outs[1])


def test_shed_outputs_bit_exact_and_in_order():
    """SLA shedding: shed frames have no output; the survivors' outputs
    are bit-exact to their micro-batches and stacked in request order."""
    api, cfg, params = _model("resnet18")
    plan = plan_graph(cfg.graph(), F(3), n_stages=2)
    frames = _frames(40)
    tr = Tracer()
    eng = CNNStreamEngine(
        cfg.graph(), params, plan,
        ServeConfig(microbatch=4, jit=False, trace=tr,
                    arrival=adversarial(best_rate_frames(plan), margin=F(2)),
                    overload=ShedPolicy(F(12))))
    eng.submit_all(frames)
    rep = eng.run()
    assert rep.shed > 0 and rep.completed + rep.shed == 40
    shed = set(rep.shed_rids)
    assert all((r.out is None) == (r.rid in shed) for r in eng._requests)
    kept = [r for r in range(40) if r not in shed]
    ref = _reference(_served_batches(tr), _engine_rungs(eng), frames, 4,
                     cfg.dtype)
    assert sorted(ref) == kept
    assert np.array_equal(eng.outputs(), np.stack([ref[r] for r in kept]))
    assert max(_in_flight(tr)) == 2


def test_switch_ladder_outputs_bit_exact():
    """A ``SwitchPolicy`` rung switch: batches still uncollected from
    the old rung stay valid; each frame matches its own rung's plan."""
    api, cfg, params = _model("mobilenet_v2", hw=32, num_classes=10)
    graph = cfg.graph()
    ladder = PlanLadder.build(graph, F(2), n_stages=2, rate_factors=(1, 2))
    plan = ladder.rungs[0].plan
    frames = _frames(10, hw=32)
    tr = Tracer()
    eng = CNNStreamEngine(
        graph, params, plan,
        ServeConfig(microbatch=2, jit=False, trace=tr,
                    arrival=bursty(2 * best_rate_frames(plan), burst=8,
                                   gap=12),
                    overload=SwitchPolicy(ladder, window_ticks=F(4))))
    eng.submit_all(frames)
    rep = eng.run()
    assert rep.completed == 10 and rep.switches
    batches = _served_batches(tr)
    assert len({rung for rung, _ in batches}) >= 2
    ref = _reference(batches, _engine_rungs(eng), frames, 2, cfg.dtype)
    assert np.array_equal(eng.outputs(), np.stack([ref[r] for r in range(10)]))
    assert max(_in_flight(tr)) == 2


def test_fleet_two_tenants_bit_exact():
    """Two engines interleaved by ``FleetScheduler``: every output is on
    the host once the scheduler's ``finish`` calls return."""
    from repro.fleet import (
        Chip, FleetScheduler, Tenant, TenantWorkload, chip_pool, plan_pool)

    tenants = (
        Tenant("a", "resnet18", F(1, 4), input_hw=(HW, HW), num_classes=4),
        Tenant("b", "mobilenet_v1", F(1, 4), input_hw=(HW, HW),
               num_classes=4),
    )
    pool = plan_pool(tenants, (Chip("big0", bram36=4096),) + chip_pool(3),
                     s_options=(1,))
    tr = Tracer()
    sched = FleetScheduler(pool, config=ServeConfig(execute=True, trace=tr))
    sched.init_params("a", jax.random.PRNGKey(0))
    sched.init_params("b", jax.random.PRNGKey(1))
    frames = {"a": _frames(7, seed=2), "b": _frames(5, seed=3)}
    tenant_cfg = ServeConfig(microbatch=2, jit=False)
    rep = sched.serve([TenantWorkload(n, frames[n], config=tenant_cfg)
                       for n in ("a", "b")])
    for name in ("a", "b"):
        cand = pool.candidate_for(name)
        params = sched.params[name]
        if cand.plan.replications:
            params = replicate_params(params, cand.plan.replications)
        n = len(frames[name])
        ref = _reference(_served_batches(tr, pid=name),
                         [(cand.plan.graph, params, None)], frames[name], 2,
                         cand.cfg.dtype)
        assert np.array_equal(rep.outputs[name],
                              np.stack([ref[r] for r in range(n)]))
        assert max(_in_flight(tr, pid=name)) == 2
        fetch = tr.spans("fetch", pid=name, clock="host")
        assert sorted(s.arg("bid") for s in fetch) == list(range((n + 1) // 2))
