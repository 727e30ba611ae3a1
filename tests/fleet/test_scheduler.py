"""Multi-tenant fleet scheduler (fleet/scheduler.py).

Acceptance surface: >= 2 families served concurrently on one
heterogeneous chip budget, zero stalls at <= each tenant's BestRate,
per-tenant results identical to standalone runs (tenants share the
clock, never chips), and an execute=True run whose outputs match the
plain executor.
"""
from fractions import Fraction as F

import jax
import numpy as np
import pytest

from repro.fleet import (
    Chip,
    FleetError,
    FleetScheduler,
    Tenant,
    TenantWorkload,
    chip_pool,
    plan_pool,
)
from repro.models.registry import get_cnn_api
from repro.serving import ServeConfig
from repro.serving.cnn_stream import best_rate_frames

TENANTS = (
    Tenant("alpha", "resnet18", F(1, 2), input_hw=(32, 32), num_classes=10),
    Tenant("beta", "mobilenet_v2", F(1, 2), input_hw=(32, 32), num_classes=10),
)
CHIPS = (Chip("big0", bram36=4096),) + chip_pool(4)


@pytest.fixture(scope="module")
def pool():
    return plan_pool(TENANTS, CHIPS, s_options=(1, 2), try_replicate=True)


def _workloads(pool, frac=F(1)):
    """Per-tenant loads at ``frac`` x that tenant's own BestRate."""
    out = []
    for name, frames in (("alpha", 24), ("beta", 16)):
        br = best_rate_frames(pool.candidate_for(name).plan)
        out.append(TenantWorkload(name, frames, arrival_rate=frac * br))
    return out


def test_two_families_zero_stalls_at_best_rate(pool):
    sched = FleetScheduler(pool, config=ServeConfig(execute=False))
    rep = sched.serve(_workloads(pool, frac=F(1)))
    assert set(rep.reports) == {"alpha", "beta"}
    assert rep.all_stall_free
    assert rep.all_within_bounds
    for r in rep.reports.values():
        assert r.completed == r.frames
        assert r.admitted_rate == r.arrival_rate  # <= BestRate: no throttle


def test_fleet_matches_standalone(pool):
    """Tenants share the clock but not chips, so the fleet run of each
    tenant is event-for-event its standalone run."""
    sched = FleetScheduler(pool, config=ServeConfig(execute=False))
    workloads = _workloads(pool, frac=F(1, 2))
    fleet = sched.serve(workloads)
    for w in workloads:
        # the engine the fleet builds for w: arrival at w.arrival_rate
        solo = sched._engine(w).run()
        got = fleet.reports[w.tenant]
        assert got.makespan_ticks == solo.makespan_ticks
        assert got.latency_ticks == solo.latency_ticks
        assert got.service_latency_ticks == solo.service_latency_ticks
        assert [s.busy_cycles for s in got.stages] == [
            s.busy_cycles for s in solo.stages
        ]


def test_chip_occupancy_over_fleet_makespan(pool):
    sched = FleetScheduler(pool, config=ServeConfig(execute=False))
    rep = sched.serve(_workloads(pool))
    assert set(rep.chip_occupancy) == {c.name for c in CHIPS}
    for name in pool.spare_chips:
        assert rep.chip_occupancy[name] == 0.0
    for a in pool.assignments:
        busy = rep.reports[a.tenant].stages[a.stage].busy_cycles
        want = float(busy / rep.makespan_cycles)
        assert rep.chip_occupancy[a.chip] == pytest.approx(want)
        assert 0 < rep.chip_occupancy[a.chip] <= 1


def test_execute_outputs_match_plain_apply():
    tenants = (
        Tenant("a", "resnet18", F(1, 4), input_hw=(16, 16), num_classes=4),
        Tenant("b", "mobilenet_v1", F(1, 4), input_hw=(16, 16),
               num_classes=4),
    )
    pool = plan_pool(tenants, (Chip("big0", bram36=4096),) + chip_pool(3),
                     s_options=(1, 2))
    sched = FleetScheduler(pool, config=ServeConfig(execute=True))
    sched.init_params("a", jax.random.PRNGKey(0))
    sched.init_params("b", jax.random.PRNGKey(1))
    rng = np.random.default_rng(0)
    fa = rng.standard_normal((5, 16, 16, 3)).astype(np.float32)
    fb = rng.standard_normal((3, 16, 16, 3)).astype(np.float32)
    rep = sched.serve([TenantWorkload("a", fa), TenantWorkload("b", fb)])
    assert rep.all_stall_free
    for name, frames, fam in (("a", fa, "resnet18"), ("b", fb,
                                                      "mobilenet_v1")):
        api = get_cnn_api(fam)
        ref = np.asarray(
            api.apply(sched.params[name], frames,
                      pool.candidate_for(name).cfg))
        np.testing.assert_allclose(rep.outputs[name], ref, rtol=1e-4,
                                   atol=1e-4)


def test_scheduler_validation_errors(pool):
    sched = FleetScheduler(pool, config=ServeConfig(execute=False))
    with pytest.raises(FleetError, match="no workloads"):
        sched.serve([])
    with pytest.raises(FleetError, match="unpooled tenant"):
        sched.serve([TenantWorkload("nobody", 4)])
    with pytest.raises(FleetError, match="duplicate workload"):
        sched.serve([TenantWorkload("alpha", 4), TenantWorkload("alpha", 4)])
    with pytest.raises(FleetError, match="no params"):
        FleetScheduler(pool, config=ServeConfig(execute=True)).serve(
            [TenantWorkload("alpha", 4)])
