"""ServeConfig (serving/config.py): the one serving surface.

Pins the contract: the config is frozen, and ``flush_after_ticks``
threads through every front door (engine, ``CNNApi.serve``,
``FleetScheduler`` — including per-tenant ``TenantWorkload.config``
with its own overload policy).
"""
import dataclasses
from fractions import Fraction as F

import pytest

from repro.core.graph import plan_graph
from repro.fleet import (
    Chip,
    FleetScheduler,
    Tenant,
    TenantWorkload,
    chip_pool,
    plan_pool,
)
from repro.models.registry import get_cnn_api
from repro.serving import ServeConfig, ShedPolicy
from repro.serving.cnn_stream import ServingError, best_rate_frames
from repro.serving.scenarios import adversarial


def _setup(family="resnet18", n_stages=2, rate=F(3)):
    api = get_cnn_api(family)
    cfg = api.make_config(input_hw=(32, 32), num_classes=10)
    graph = cfg.graph()
    return api, cfg, graph, plan_graph(graph, rate, n_stages=n_stages)


def test_config_is_frozen_and_with_copies():
    cfg = ServeConfig(microbatch=2)
    with pytest.raises(dataclasses.FrozenInstanceError):
        cfg.microbatch = 4
    cfg2 = cfg.with_(microbatch=4, arrival=F(3))
    assert cfg2.microbatch == 4 and cfg2.arrival == F(3)
    assert cfg.microbatch == 2  # original untouched


def test_api_serve_threads_config_and_flush():
    """CNNApi.serve accepts config= (incl. flush_after_ticks) — the
    partial micro-batch flushes on the straggler bound instead of
    waiting for the stream end."""
    api, cfg, _, _ = _setup()
    _, rep = api.serve(
        None, 9, cfg, input_rate=F(3), n_stages=2,
        config=ServeConfig(microbatch=4, execute=False, arrival=F(1, 4),
                           flush_after_ticks=F(2)))
    assert rep.completed == 9
    assert rep.microbatch == 4
    # flush bound 2 ticks < inter-arrival 4 ticks: every frame flushes
    # alone instead of waiting to fill the 4-frame batch
    assert all(s.batches_served >= 3 for s in rep.stages)


@pytest.fixture(scope="module")
def pool():
    tenants = (
        Tenant("alpha", "resnet18", F(1, 2), input_hw=(32, 32),
               num_classes=10),
        Tenant("beta", "mobilenet_v2", F(1, 2), input_hw=(32, 32),
               num_classes=10),
    )
    chips = (Chip("big0", bram36=4096),) + chip_pool(4)
    return plan_pool(tenants, chips, s_options=(1, 2), try_replicate=False)


def test_fleet_scheduler_takes_config(pool):
    sched = FleetScheduler(pool, config=ServeConfig(execute=False))
    rep = sched.serve([
        TenantWorkload("alpha", 8, flush_after_ticks=F(1)),
        TenantWorkload("beta", 6, arrival_rate=F(1, 2)),
    ])
    assert rep.reports["alpha"].completed == 8
    assert rep.reports["beta"].completed == 6
    # unified schema: per-tenant summaries + canonical rows
    rows = dict(rep.to_rows())
    assert "alpha/served" in rows and "beta/latency" in rows


def test_fleet_per_tenant_policy(pool):
    """TenantWorkload.config carries a per-tenant overload policy: one
    tenant sheds under its SLA while the other serves normally."""
    alpha_plan = pool.chosen["alpha"].plan
    br = best_rate_frames(alpha_plan)
    shed_cfg = ServeConfig(
        execute=False,
        arrival=adversarial(br, margin=F(3, 2)),
        overload=ShedPolicy(deadline_ticks=F(12)),
    )
    sched = FleetScheduler(pool, config=ServeConfig(execute=False))
    rep = sched.serve([
        TenantWorkload("alpha", 120, config=shed_cfg),
        TenantWorkload("beta", 8),
    ])
    a, b = rep.reports["alpha"], rep.reports["beta"]
    assert a.shed > 0 and a.completed + a.shed == 120
    assert a.within_queue_bounds
    assert b.shed == 0 and b.completed == 8
    assert b.stall_free


def test_workload_config_excludes_legacy_fields(pool):
    with pytest.raises(ServingError):
        TenantWorkload("alpha", 8, arrival_rate=F(2), config=ServeConfig())
