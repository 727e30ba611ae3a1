"""``bench/run.py`` refuses to measure without a TPU, and the rest of a
run decides ``correct`` from what the timed path returned: at a small
size on the CPU, a sound run is correct and each planted fault in the
served path makes it false."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from bench import harness
from repro.serving import cnn_stream

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def test_cpu_backend_is_refused():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "resnet18.saturate",
         "--seed", str(2**31 + 3), "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())


def test_unknown_device_kind_is_refused():
    with pytest.raises(harness.BenchError, match="no peaks"):
        harness.peak_of("TPU v99")
    assert harness.peak_of("TPU v5 lite")["flops"] == 197e12


def test_every_workload_has_its_files():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    for wl in bench["workloads"]:
        cell = harness.load_cell(wl["name"])
        assert 0 < cell.config["limits"]["logit_err_mean"] < cell.config["limits"]["logit_err"]
        assert cell.metrics("end_to_end") and cell.metrics("per_layer")
    with pytest.raises(harness.BenchError, match="unknown workload"):
        harness.load_cell("resnet18.nothing")


@pytest.fixture(scope="module")
def served():
    """resnet18 through the served rate-matched path at 32x32, micro-batch
    2, 4 frames a call from a pool of 8 (Pallas kernels interpreted)."""
    cell = harness.load_cell("resnet18.saturate")
    cell.config.update(input_hw=[32, 32], num_classes=10, microbatch=2)
    cell.mix.update(pool_frames=8, frames_per_call=4)
    return harness.setup(cell, 2**31 + 17)


def _run(served):
    window = harness.measure(served, 5, 0.5)
    return harness.verify(served, window)


def test_sound_run_is_correct(served):
    verdict = _run(served)
    assert verdict["correct"], verdict
    assert verdict["failed"] == 0 and verdict["attempted"] > 0


def _altered(self, batch, t, orig=cnn_stream.CNNStreamEngine._finish_batch):
    """A logit altered where it is produced."""
    orig(self, batch, t)
    f = batch.frames[0]
    f.out = f.out.copy()
    f.out[0] += 0.1 * np.abs(f.out).max()


def _half_batch(self, batch, t, orig=cnn_stream.CNNStreamEngine._finish_batch):
    """Half of each micro-batch left out: its frames get the other half's
    logits."""
    orig(self, batch, t)
    half = len(batch.frames) // 2
    for i, f in enumerate(batch.frames[half:]):
        f.out = batch.frames[i].out


def _swapped(self, batch, t, orig=cnn_stream.CNNStreamEngine._finish_batch):
    """Two frames' answers swapped."""
    orig(self, batch, t)
    a, b = batch.frames[0], batch.frames[-1]
    a.out, b.out = b.out, a.out


def _dropped(self, orig=cnn_stream.CNNStreamEngine.outputs):
    """A frame that never comes back."""
    return orig(self)[:-1]


@pytest.mark.parametrize("attr, fault", [
    ("_finish_batch", _altered),
    ("_finish_batch", _half_batch),
    ("_finish_batch", _swapped),
    ("outputs", _dropped),
])
def test_planted_fault_is_not_correct(served, monkeypatch, attr, fault):
    monkeypatch.setattr(cnn_stream.CNNStreamEngine, attr, fault)
    verdict = _run(served)
    assert not verdict["correct"], verdict
    assert verdict["failed"] > 0
