"""Table X (beyond-paper): wall-clock multi-device staged execution.

The tick tables (6-8) judge serving on the deterministic event model;
this table judges it on *measured* silicon: ``DevicePipeline`` places
the stages of the S-chip partition round-robin over the host's devices
(``placement=len(jax.devices())``: co-resident, by request, where the
host has fewer devices than stages), pumps M micro-batches through the GPipe schedule
with async dispatch + double-buffered boundary transfers, and reports
frames/sec against a per-micro-batch blocking sequential pass over the
same compiled stages.

Two row kinds, deliberately split for the regression gate:

  * **structural** (``/placement``) — pure arithmetic: the round-robin
    stage->device ordinals for 2- and 4-device hosts and the schedule's
    M/(M+S-1) utilization bound.  Identical on every machine — pinned
    in benchmarks/baselines/ like every analytic table.
  * **measured** (``/wallclock``) — warmed-up wall-clock fps, overlap
    speedup, per-stage busy fractions, and the live device count.
    Timing noise is not a regression: these rows are excluded from
    gating (check_regression's ``/wallclock`` default exclude), and the
    only sanity applied here is a *non-gating* stderr warning when the
    overlapped schedule falls below 0.9x sequential — on a one-device
    CI host both schedules share a queue, so ~1.0x is the expectation,
    not a failure.
"""
from __future__ import annotations

import sys
import time
from fractions import Fraction as F

import jax

from repro.core.stage_partition import round_robin_placement
from repro.distributed.device_pipeline import DevicePipeline
from repro.distributed.pipeline_parallel import microbatch_utilization
from repro.models.registry import get_cnn_api

FAMILIES = ("resnet18", "resnet34", "mobilenet_v1", "mobilenet_v2")
MEASURED = ("resnet18", "mobilenet_v2")
STAGES = (2, 3)
RATE = F(3)
FRAMES = 8        # M = 8 micro-batches of 1 frame each
MICROBATCH = 1


def _structural_rows() -> list:
    rows = []
    for family in FAMILIES:
        for s in STAGES:
            t0 = time.perf_counter()
            p2 = list(round_robin_placement(s, 2))
            p4 = list(round_robin_placement(s, 4))
            util = microbatch_utilization(FRAMES, s)
            dt = (time.perf_counter() - t0) * 1e6
            rows.append((
                f"table10/{family}/S{s}/placement", dt,
                f"2-dev {p2}, 4-dev {p4}, "
                f"util bound M={FRAMES}: {util:.4f}"))
    return rows


def _measured_rows() -> list:
    rows = []
    for family in MEASURED:
        api = get_cnn_api(family)
        cfg = api.make_config(input_hw=(32, 32), num_classes=10)
        params = api.init(cfg, jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (FRAMES, 32, 32, 3))
        for s in STAGES[:1]:  # S=2 keeps the CI timing budget honest
            plan = api.partition(cfg, RATE, s)
            dp = DevicePipeline.build(
                api.graph(cfg), params, partition=plan,
                placement=len(jax.devices()),
                cache=api.caches["pipelines"])
            rep = dp.measure(x, microbatch=MICROBATCH, warmup=1, repeats=2)
            busy = ", ".join(f"{f:.2f}" for f in rep.stage_busy_frac)
            rows.append((
                f"table10/{family}/S{s}/wallclock", rep.overlap_s * 1e6,
                f"{rep.fps_overlap:.1f} fps overlapped vs "
                f"{rep.fps_sequential:.1f} sequential "
                f"({rep.speedup:.2f}x, bound {rep.utilization_bound:.3f}), "
                f"busy/stage [{busy}], {rep.n_devices} device(s), "
                f"placement {list(rep.placement)}"))
            if rep.speedup < 0.9:
                # non-gating: a shared single-device queue plus schedule
                # bookkeeping can dip below 1x; flag it, don't fail CI
                print(
                    f"table10: WARNING {family} S{s} overlap "
                    f"{rep.speedup:.2f}x < 0.9x sequential "
                    f"({rep.n_devices} device(s))", file=sys.stderr)
    return rows


def run() -> list:
    return _structural_rows() + _measured_rows()


if __name__ == "__main__":
    for name, us, derived in run():
        print(f"{name},{us:.1f},{derived}")
