"""Validate the analytic FLOPs model against UNROLLED compiles.

core/flops.py corrects XLA's loop-bodies-once counting; this test is the
calibration evidence: on a small config with scan_layers=False and
grad_accum=1 (nothing scanned), measured HLO FLOPs must agree with
step_flops within tolerance.
"""
import dataclasses

import jax
import pytest

from repro.configs.registry import get_config, reduced
from repro.configs.shapes import ShapeSuite
from repro.core.flops import step_flops
from repro.core.hlo_analysis import normalize_cost_analysis
from repro.launch.train import adam_config_for, build_train_step
from repro.models import lm_api
from repro.optim import optimizers as opt


def _measured_train_flops(cfg, shape):
    api = lm_api.get_api(cfg)
    adam = adam_config_for(cfg)
    params = api.init(cfg, jax.random.key(0))
    opt_state = opt.init(adam, params)
    batch = jax.tree.map(
        lambda s: jax.numpy.zeros(s.shape, s.dtype),
        lm_api.train_batch_specs(cfg, shape))
    step = build_train_step(cfg, adam)
    compiled = jax.jit(step).lower(params, opt_state, batch).compile()
    return float(normalize_cost_analysis(compiled.cost_analysis())["flops"])


@pytest.mark.parametrize("arch", ["qwen2-7b", "starcoder2-15b"])
def test_train_flops_match_unrolled(arch):
    cfg = reduced(get_config(arch), layers=2, d_model=128, vocab=512)
    cfg = dataclasses.replace(cfg, scan_layers=False, grad_accum=1,
                              remat=True)
    shape = ShapeSuite("t", seq_len=128, global_batch=4, kind="train")
    measured = _measured_train_flops(cfg, shape)
    analytic = step_flops(cfg, shape)
    ratio = analytic / measured
    # optimizer elementwise flops + norm transcendentals are not modelled;
    # agreement within 30% validates the big terms (matmuls dominate).
    assert 0.7 < ratio < 1.3, f"{arch}: analytic/measured = {ratio:.3f}"


def test_scan_undercount_is_real():
    """The raison d'être: the SAME model with scan_layers=True reports
    fewer HLO FLOPs (bodies counted once) — the correction is needed."""
    cfg = reduced(get_config("qwen2-7b"), layers=4, d_model=128, vocab=512)
    shape = ShapeSuite("t", seq_len=128, global_batch=4, kind="train")
    scanned = _measured_train_flops(
        dataclasses.replace(cfg, scan_layers=True, grad_accum=2), shape)
    unrolled = _measured_train_flops(
        dataclasses.replace(cfg, scan_layers=False, grad_accum=1), shape)
    assert scanned < 0.6 * unrolled


def test_moe_flops_track_capacity():
    cfg = reduced(get_config("grok-1-314b"), layers=2, d_model=128,
                  vocab=512)
    cfg = dataclasses.replace(cfg, scan_layers=False, grad_accum=1)
    shape = ShapeSuite("t", seq_len=128, global_batch=4, kind="train")
    measured = _measured_train_flops(cfg, shape)
    analytic = step_flops(cfg, shape)
    ratio = analytic / measured
    assert 0.6 < ratio < 1.4, f"grok-reduced: analytic/measured = {ratio:.3f}"
