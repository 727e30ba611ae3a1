"""DevicePipeline: placement resolution, GPipe schedule correctness,
donation/double-buffering safety, the wall-clock report, and the
serving engine's execute="devices" path — all on the single-CPU host,
with stages co-resident by request (``placement=len(jax.devices())``,
``n_devices=``); asking for more distinct devices than exist raises.
Genuine multi-device overlap is exercised by examples/pipeline_demo.py's
4-virtual-CPU-device child."""

from fractions import Fraction as F

import jax
import numpy as np
import pytest

from repro.core.graph import GraphError, plan_graph
from repro.core.stage_partition import round_robin_placement
from repro.distributed.device_pipeline import (
    DevicePipeline,
    DevicePipelineError,
    device_placement_rows,
)
from repro.models import cnn
from repro.models.registry import get_cnn_api


@pytest.fixture(scope="module")
def resnet():
    api = get_cnn_api("resnet18")
    cfg = api.make_config(input_hw=(32, 32), num_classes=10)
    params = api.init(cfg, jax.random.PRNGKey(0))
    x = np.asarray(
        jax.random.normal(jax.random.PRNGKey(1), (4, 32, 32, 3)),
        dtype=np.float32,
    )
    return api, cfg, params, x


# ---------------------------------------------------------------------------
# placement resolution
# ---------------------------------------------------------------------------


def test_round_robin_placement_math():
    assert round_robin_placement(4, 2) == (0, 1, 0, 1)
    assert round_robin_placement(2, 4) == (0, 1)
    assert round_robin_placement(1, 1) == (0,)
    with pytest.raises(ValueError):
        round_robin_placement(0, 2)
    with pytest.raises(ValueError):
        round_robin_placement(2, 0)


def test_plan_graph_records_placement(resnet):
    api, cfg, _, _ = resnet
    plan = plan_graph(api.graph(cfg), F(1), n_stages=3, n_devices=2)
    assert plan.stage_plan.placement == (0, 1, 0)
    # placement is per stage: n_devices without n_stages is an error
    with pytest.raises(GraphError):
        plan_graph(api.graph(cfg), F(1), n_devices=2)


def test_resolve_stage_devices_forms():
    devs = jax.devices()
    n = len(devs)
    # None/False: unplaced
    assert cnn.resolve_stage_devices(None, 3) is None
    assert cnn.resolve_stage_devices(False, 3) is None
    # int: round-robin over the first n devices
    got = cnn.resolve_stage_devices(1, 3)
    assert got == (devs[0],) * 3
    got = cnn.resolve_stage_devices(n, n + 1)
    assert got == tuple(devs[s % n] for s in range(n + 1))
    # ordinal sequence: cycled over the stages; repeats co-locate
    assert cnn.resolve_stage_devices((0,), 3) == (devs[0],) * 3
    got = cnn.resolve_stage_devices(tuple(range(n)), n)
    assert got == tuple(devs)
    # explicit Device objects round-robin
    got = cnn.resolve_stage_devices((devs[0],), 3)
    assert got == (devs[0],) * 3
    with pytest.raises(cnn.GraphExecutionError):
        cnn.resolve_stage_devices(0, 3)
    with pytest.raises(cnn.GraphExecutionError):
        cnn.resolve_stage_devices((), 3)
    # more distinct devices than exist: an error, never a silent fold
    with pytest.raises(cnn.GraphExecutionError, match="exist"):
        cnn.resolve_stage_devices(n + 1, n + 1)
    with pytest.raises(cnn.GraphExecutionError, match="exist"):
        cnn.resolve_stage_devices(tuple(range(n + 1)), n + 1)
    with pytest.raises(cnn.GraphExecutionError, match="exist"):
        cnn.resolve_stage_devices(True, n + 1)


def test_device_pipeline_requires_placement(resnet):
    api, cfg, params, _ = resnet
    plan = api.partition(cfg, F(1), 2)
    pipe = cnn.stage_functions(api.graph(cfg), partition=plan)
    with pytest.raises(DevicePipelineError):
        DevicePipeline(pipe, params, placement=None)


def test_device_placement_rows_structural():
    assert device_placement_rows(3, 2) == [
        ("stage0_dev", 0),
        ("stage1_dev", 1),
        ("stage2_dev", 0),
    ]


# ---------------------------------------------------------------------------
# schedule correctness (single-CPU host: stages co-resident by request)
# ---------------------------------------------------------------------------


def test_gpipe_matches_staged_forward(resnet):
    api, cfg, params, x = resnet
    graph = api.graph(cfg)
    plan = api.partition(cfg, F(1), 3)
    sf = cnn.staged_forward(graph, partition=plan)
    ref = np.asarray(sf(params, x)["fc"])
    dp = DevicePipeline.build(
        graph, params, partition=plan, placement=len(jax.devices())
    )
    assert dp.n_stages == 3
    assert len(dp.placement_ordinals()) == 3
    for mb in (1, 2, 4):  # M = 4, 2, 1 (1 micro-batch = degenerate schedule)
        out = np.asarray(dp.run(x, microbatch=mb))
        np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)


def test_single_stage_degenerate(resnet):
    api, cfg, params, x = resnet
    graph = api.graph(cfg)
    plan = api.partition(cfg, F(1), 1)
    dp = DevicePipeline.build(graph, params, partition=plan, placement=True)
    ref = np.asarray(api.apply(params, x, cfg))
    out = np.asarray(dp.run(x, microbatch=2))
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)


def test_repeated_runs_and_donation_safety(resnet):
    # donated transfers must never leave a deleted array reachable: the
    # same DevicePipeline re-runs on fresh and on identical inputs
    api, cfg, params, x = resnet
    plan = api.partition(cfg, F(1), 2)
    dp = DevicePipeline.build(
        api.graph(cfg), params, partition=plan, placement=len(jax.devices())
    )
    a = np.asarray(dp.run(x, microbatch=2))
    b = np.asarray(dp.run(x, microbatch=2))
    np.testing.assert_array_equal(a, b)
    c = np.asarray(dp.run(x[::-1], microbatch=2))
    np.testing.assert_allclose(c, a[::-1], atol=1e-5, rtol=1e-5)


def test_measure_report_fields(resnet):
    api, cfg, params, x = resnet
    plan = api.partition(cfg, F(1), 2)
    dp = DevicePipeline.build(
        api.graph(cfg), params, partition=plan, placement=len(jax.devices())
    )
    rep = dp.measure(x, microbatch=1, warmup=1, repeats=1)
    assert rep.frames == 4 and rep.n_micro == 4 and rep.n_stages == 2
    assert rep.microbatch == 1
    assert rep.utilization_bound == pytest.approx(4 / 5)
    assert len(rep.placement) == 2 and rep.n_devices >= 1
    assert rep.overlap_s > 0 and rep.sequential_s > 0
    assert rep.fps_overlap > 0 and rep.speedup > 0
    assert len(rep.stage_busy_s) == 2
    assert all(b > 0 for b in rep.stage_busy_s)


# ---------------------------------------------------------------------------
# serving engine: execute="devices"
# ---------------------------------------------------------------------------


def test_serve_execute_devices_matches_host(resnet):
    from repro.serving.cnn_stream import serve_frames
    from repro.serving.config import ServeConfig

    api, cfg, params, x = resnet
    graph = api.graph(cfg)
    host, _ = serve_frames(
        graph, params, x, input_rate=F(1), n_stages=2,
        config=ServeConfig(execute=True, microbatch=2),
    )
    placed, rep = serve_frames(
        graph, params, x, input_rate=F(1), n_stages=2,
        n_devices=len(jax.devices()),
        config=ServeConfig(execute="devices", microbatch=2),
    )
    np.testing.assert_allclose(placed, host, atol=1e-5, rtol=1e-5)
    assert rep.completed == 4


def test_serve_rejects_unknown_execute(resnet):
    from repro.serving.cnn_stream import ServingError, serve_frames
    from repro.serving.config import ServeConfig

    api, cfg, params, x = resnet
    with pytest.raises(ServingError):
        serve_frames(
            api.graph(cfg), params, x, input_rate=F(1), n_stages=2,
            config=ServeConfig(execute="device"),
        )
