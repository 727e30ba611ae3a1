"""DevicePipeline vs staged_forward exactness matrix.

All four CNN families x S in {2, 3}: the GPipe device schedule must
compute the same network as the sequential staged executor — allclose
in fp32, and **bit-exact** with int8 quantized cut crossings when the
comparison is at matched micro-batch granularity (the per-tensor
dynamic link scales include the batch dim, so the reference must see
the same micro-batches the schedule pumps).  Stages are placed round-robin
over this host's devices (``placement=len(jax.devices())``): on the
single-CPU host they are co-resident by request, schedule and transfers
exercised in full."""

from fractions import Fraction as F

import jax
import numpy as np
import pytest

from repro.distributed.device_pipeline import DevicePipeline
from repro.models import cnn
from repro.models.registry import get_cnn_api

FAMILIES = ("resnet18", "resnet34", "mobilenet_v1", "mobilenet_v2")
STAGES = (2, 3)
MB = 2  # micro-batch rows; 4 frames -> M=2 micro-batches


@pytest.fixture(scope="module")
def workloads():
    out = {}
    for family in FAMILIES:
        api = get_cnn_api(family)
        cfg = api.make_config(input_hw=(16, 16), num_classes=7)
        params = api.init(cfg, jax.random.PRNGKey(0))
        x = np.asarray(
            jax.random.normal(jax.random.PRNGKey(1), (4, 16, 16, 3)),
            dtype=np.float32,
        )
        out[family] = (api, cfg, params, x)
    return out


@pytest.mark.parametrize("family", FAMILIES)
@pytest.mark.parametrize("n_stages", STAGES)
def test_fp32_allclose(workloads, family, n_stages):
    api, cfg, params, x = workloads[family]
    graph = api.graph(cfg)
    plan = api.partition(cfg, F(1), n_stages)
    sf = cnn.staged_forward(graph, partition=plan)
    dp = DevicePipeline.build(
        graph, params, partition=plan, placement=len(jax.devices())
    )
    ref = np.asarray(sf(params, x)[dp.pipeline.out_name])
    got = np.asarray(dp.run(x, microbatch=MB))
    np.testing.assert_allclose(got, ref, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("family", FAMILIES)
@pytest.mark.parametrize("n_stages", STAGES)
def test_int8_links_bit_exact(workloads, family, n_stages):
    api, cfg, params, x = workloads[family]
    graph = api.graph(cfg)
    plan = api.partition(cfg, F(1), n_stages, link_dtype="int8")
    sf = cnn.staged_forward(graph, partition=plan, link_quant=True)
    dp = DevicePipeline.build(
        graph,
        params,
        partition=plan,
        placement=len(jax.devices()),
        link_quant=True,
    )
    out = dp.pipeline.out_name
    ref = np.concatenate(
        [np.asarray(sf(params, x[i : i + MB])[out]) for i in range(0, 4, MB)]
    )
    got = np.asarray(dp.run(x, microbatch=MB))
    np.testing.assert_array_equal(got, ref)
