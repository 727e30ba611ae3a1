"""Table VII (beyond-paper): Multi-CLP replication + the chip-pool fleet.

Three row groups, all produced by exact analytic models (no JAX, no
wall-clock in any pinned column — the ``us`` timing column is machine-
dependent and ignored by the regression gate as always):

* ``replicate`` — the Multi-CLP headline: for ResNet-18 at 224x224,
  rate r = 3, S = 3 chips, contiguous min-bottleneck partitioning is
  capped by the dominant node of the bottleneck stage.  The replication
  DSE (``core.replicate.best_replication``) clones that node R ways
  behind a round-robin splitter / order-preserving merger and re-runs
  the partition DP; the row pins the strict bottleneck improvement at
  equal total arithmetic.
* ``pool`` — the chip-pool planner packing two rate-targeted tenants
  (ResNet-18 + MobileNetV2 at r = 1/2) onto a heterogeneous budget
  (one big-BRAM chip + four stock xcvu37p): chosen plan, chip
  assignments, spare chips, and the advisory cost-proportional fair
  share for comparison.
* ``fleet`` — the multi-tenant serving loop on that pool: both tenants
  pumped on one shared deterministic clock, per-tenant BestRate
  admission, zero stalls at <= the target rate, per-chip occupancy.
* ``fleet/wallclock`` — the same pool executed for real (execute=True
  with the shared ``obs.Tracer`` on): per-tenant measured fps over the
  host-clock envelope from the tenant's first ``ingest`` span to its
  last ``fetch`` span (outputs back on the host, so the device's work
  is inside it), next to the tick-domain throughput.
  Measured rows match check_regression's ``/wallclock`` default
  exclude — timing noise is not a regression.
"""
from __future__ import annotations

import time
from fractions import Fraction as F

import jax
import numpy as np

from repro.core.graph import plan_graph
from repro.core.replicate import best_replication
from repro.fleet import (
    Chip,
    FleetScheduler,
    Tenant,
    TenantWorkload,
    chip_pool,
    plan_pool,
)
from repro.models.registry import get_cnn_api
from repro.serving import ServeConfig

# the pinned Multi-CLP scenario: ResNet-18, ImageNet-size frames, the
# 3-chip partition at a rate with divisor-granularity headroom
REP_FAMILY = "resnet18"
REP_RATE = F(3)
REP_STAGES = 3

# the pinned fleet scenario: two tenants on a heterogeneous pool (the
# ResNet tail stage needs more BRAM36 than a stock chip offers)
TENANTS = (
    Tenant("alpha", "resnet18", F(1, 2), input_hw=(32, 32), num_classes=10),
    Tenant("beta", "mobilenet_v2", F(1, 2), input_hw=(32, 32), num_classes=10),
)
CHIPS = (Chip("big0", bram36=4096),) + chip_pool(4)
WORKLOADS = (
    TenantWorkload("alpha", 24, arrival_rate=F(1)),
    TenantWorkload("beta", 16, arrival_rate=F(1, 2)),
)


def _replicate_rows() -> list:
    rows = []
    api = get_cnn_api(REP_FAMILY)
    graph = api.graph(api.make_config())
    t0 = time.perf_counter()
    base = plan_graph(graph, REP_RATE, n_stages=REP_STAGES)
    dt = (time.perf_counter() - t0) * 1e6
    rows.append((
        f"table7/replicate/{REP_FAMILY}/S{REP_STAGES}/base", dt,
        f"stage mults {base.stage_mults()}, bottleneck "
        f"{max(base.stage_mults())}, total {base.total_mults}"))
    t0 = time.perf_counter()
    rep = best_replication(graph, REP_RATE, n_stages=REP_STAGES)
    dt = (time.perf_counter() - t0) * 1e6
    what = (
        f"{rep.replications[0].node} x{rep.replications[0].r}"
        if rep.replications else "none (baseline kept)"
    )
    rows.append((
        f"table7/replicate/{REP_FAMILY}/S{REP_STAGES}/best", dt,
        f"replicated {what}, stage mults {rep.stage_mults()}, bottleneck "
        f"{max(rep.stage_mults())}, total {rep.total_mults}"))
    improved = max(rep.stage_mults()) < max(base.stage_mults())
    equal_arith = rep.total_mults == base.total_mults
    verdict = "IMPROVED" if improved else "NO GAIN (bug)"
    rows.append((
        f"table7/replicate/{REP_FAMILY}/S{REP_STAGES}/verdict", 0.0,
        f"bottleneck {max(base.stage_mults())} -> {max(rep.stage_mults())} "
        f"({verdict}), equal arithmetic {equal_arith}"))
    return rows


def _pool_rows():
    rows = []
    t0 = time.perf_counter()
    pp = plan_pool(TENANTS, CHIPS, s_options=(1, 2), try_replicate=True)
    dt = (time.perf_counter() - t0) * 1e6
    for t in TENANTS:
        c = pp.chosen[t.name]
        rows.append((
            f"table7/pool/{t.name}", dt if t is TENANTS[0] else 0.0,
            f"{t.family} @ r={t.input_rate}: plan {c.label}, "
            f"mults {c.total_mults}, bottleneck {c.bottleneck_mults}"))
    placed = ", ".join(
        f"{a.chip}<-{a.tenant}.s{a.stage}(dsp {a.dsp_frac:.2f})"
        for a in pp.assignments)
    rows.append((
        "table7/pool/assignments", 0.0,
        f"{placed}; spare {len(pp.spare_chips)}/{len(CHIPS)}"))
    share = pp.fair_share()
    rows.append((
        "table7/pool/fair_share", 0.0,
        f"cost-proportional would give {share} "
        f"(exact packing uses {pp.chips_used} chips)"))
    return rows, pp


def _fleet_rows(pp) -> list:
    rows = []
    sched = FleetScheduler(pp, config=ServeConfig(execute=False))
    t0 = time.perf_counter()
    rep = sched.serve(list(WORKLOADS))
    dt = (time.perf_counter() - t0) * 1e6
    summaries = rep.summaries()
    for w in WORKLOADS:
        # the unified telemetry schema renders the pinned row verbatim
        rows.append((
            f"table7/fleet/{w.tenant}", dt if w is WORKLOADS[0] else 0.0,
            f"arr {float(w.arrival_rate):.2f} f/tick: "
            f"{summaries[w.tenant].fleet_line()}"))
    occ = ", ".join(
        f"{chip} {v:.3f}" for chip, v in sorted(rep.chip_occupancy.items()))
    rows.append((
        "table7/fleet/occupancy", 0.0,
        f"{occ} (fleet makespan, shared clock)"))
    return rows


def _fleet_wallclock_rows(pp) -> list:
    """Measured per-tenant fps: the fleet executed on live devices with
    the shared tracer recording host-clock ``ingest``/``fetch`` spans
    (first ingest to last fetch, per tenant).  A handful
    of frames per tenant keeps the CI budget honest; every value here
    is wall-clock (unpinned by the ``/wallclock`` exclude)."""
    rows = []
    sched = FleetScheduler(
        pp, config=ServeConfig(execute=True, trace=True))
    for i, t in enumerate(TENANTS):
        sched.init_params(t.name, jax.random.PRNGKey(i))
    frames = {"alpha": 6, "beta": 4}
    workloads = [
        TenantWorkload(
            t.name,
            np.random.RandomState(i)
            .randn(frames[t.name], *t.input_hw, 3)
            .astype("float32"))
        for i, t in enumerate(TENANTS)]
    t0 = time.perf_counter()
    rep = sched.serve(workloads)
    dt = (time.perf_counter() - t0) * 1e6
    summaries = rep.summaries()
    for w in workloads:
        s = summaries[w.tenant]
        rows.append((
            f"table7/fleet/wallclock/{w.tenant}",
            dt if w is workloads[0] else 0.0,
            f"measured {rep.measured_fps(w.tenant):.1f} fps over "
            f"{rep.tenant_wall_s[w.tenant]:.3f}s host wall "
            f"({s.completed} frames; tick thr {s.throughput:.3f} f/tick)"))
    return rows


def run() -> list:
    rows = _replicate_rows()
    pool_rows, pp = _pool_rows()
    rows += pool_rows
    rows += _fleet_rows(pp)
    rows += _fleet_wallclock_rows(pp)
    return rows


if __name__ == "__main__":
    for name, us, derived in run():
        print(f"{name},{us:.1f},{derived}")
