"""chip_smoke.py on the CPU: its phases at 32x32, and its refusal to
report anything without a TPU."""

import importlib.util
import json
import pathlib

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_main_refuses_cpu(smoke, capsys):
    with pytest.raises(smoke.SmokeError, match="no TPU"):
        smoke.main([])
    assert '"ok"' not in capsys.readouterr().out


def test_phases_serve_within_tolerance_at_32(smoke):
    lines = []
    smoke.run(
        1, input_hw=(32, 32), num_classes=10, frames=4, microbatch=2,
        emit=lines.append,
    )
    rows = [json.loads(s) for s in lines]
    assert rows[0]["phase"] == "reference"
    served = [r for r in rows if r["phase"] == "serve"]
    assert [(r["path"], r["stages"]) for r in served] == [
        ("xla", 1), ("xla", 2), ("pallas", 1), ("pallas", 2)
    ]
    for r in served:
        assert r["frames"] == 4
        assert r["max_rel_err"] <= 1e-5  # fp32 on the CPU
        assert r["top1_agree"] == 1.0


def test_compile_cache_is_env_dir_else_checkout(monkeypatch, tmp_path):
    import jax

    from repro.compile_cache import ENV, use_compile_cache

    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv(ENV, str(tmp_path / "env"))
        assert use_compile_cache(str(tmp_path)) == str(tmp_path / "env")
        assert jax.config.jax_compilation_cache_dir == was  # JAX reads ENV
        monkeypatch.delenv(ENV)
        want = str(tmp_path / ".jax_cache")
        assert use_compile_cache(str(tmp_path)) == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_compare_rejects_beyond_tolerance(smoke):
    import numpy as np

    ref = np.eye(3)
    assert smoke.compare(ref + 0.01, ref)["max_rel_err"] == pytest.approx(0.01)
    with pytest.raises(smoke.SmokeError, match="tolerance"):
        smoke.compare(ref + 2 * smoke.TOLERANCE, ref)
    with pytest.raises(smoke.SmokeError, match="non-finite"):
        smoke.compare(ref * np.nan, ref)
