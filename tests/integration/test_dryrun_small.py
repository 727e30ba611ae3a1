"""Dry-run machinery validation on a small in-process device grid.

Multi-device cases run in a SUBPROCESS with XLA_FLAGS=8 host devices so
the main pytest process keeps its single-CPU view (per the task spec:
smoke tests must see 1 device).
"""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path


ROOT = Path(__file__).resolve().parents[2]


def _run(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = str(ROOT / "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_cost_analysis_is_per_device():
    """Empirical anchor for hlo_analysis semantics (jax 0.8 CPU)."""
    out = _run("""
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P, NamedSharding
        mesh = jax.make_mesh((4, 2), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        w = jax.ShapeDtypeStruct((256, 512), jnp.float32,
            sharding=NamedSharding(mesh, P("data", "model")))
        x = jax.ShapeDtypeStruct((64, 256), jnp.float32,
            sharding=NamedSharding(mesh, P("data", None)))
        c = jax.jit(lambda w, x: x @ w).lower(w, x).compile()
        from repro.core.hlo_analysis import normalize_cost_analysis
        print(normalize_cost_analysis(c.cost_analysis())["flops"])
    """)
    flops = float(out.strip().splitlines()[-1])
    logical = 2 * 64 * 256 * 512
    assert flops < logical / 2, "flops should be per-device (~1/8 logical)"
    assert flops > logical / 32


def test_small_mesh_train_cell_compiles():
    """A reduced arch through the REAL dryrun.build_cell path on a 4x2
    mesh: lower + compile + memory/cost/collectives all present."""
    out = _run("""
        import jax, json
        import dataclasses
        from repro.configs.registry import get_config, reduced
        from repro.configs.shapes import ShapeSuite
        from repro.launch.dryrun import build_cell
        from repro.core.hlo_analysis import collective_bytes, normalize_cost_analysis

        cfg = reduced(get_config("qwen2-7b"), layers=2, d_model=64, vocab=256)
        cfg = dataclasses.replace(cfg, grad_accum=2)
        shape = ShapeSuite("t", seq_len=64, global_batch=8, kind="train")
        mesh = jax.make_mesh((4, 2), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        fn, args = build_cell(cfg, shape, mesh)
        with mesh:
            compiled = jax.jit(fn, donate_argnums=(0, 1)).lower(*args).compile()
            mem = compiled.memory_analysis()
            cost = normalize_cost_analysis(compiled.cost_analysis())
            hlo = compiled.as_text()
        st = collective_bytes(hlo)
        print(json.dumps({
            "temp": mem.temp_size_in_bytes,
            "flops": cost.get("flops", 0),
            "colls": st.total_count,
        }))
    """)
    rec = json.loads(out.strip().splitlines()[-1])
    assert rec["flops"] > 0
    assert rec["colls"] > 0, "sharded train step must emit collectives"


def test_serve_cells_compile_small_mesh():
    out = _run("""
        import jax, json, dataclasses
        from repro.configs.registry import get_config, reduced
        from repro.configs.shapes import ShapeSuite
        from repro.launch.dryrun import build_cell

        for arch in ("gemma3-1b", "mamba2-780m"):
            cfg = reduced(get_config(arch), layers=2, d_model=64, vocab=256)
            for kind, seq, b in (("prefill", 64, 8), ("decode", 64, 8)):
                shape = ShapeSuite("s", seq_len=seq, global_batch=b, kind=kind)
                mesh = jax.make_mesh((4, 2), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
                fn, args = build_cell(cfg, shape, mesh)
                with mesh:
                    donate = (2,) if kind == "prefill" else (1,)
                    jax.jit(fn, donate_argnums=donate).lower(*args).compile()
                print(arch, kind, "ok")
    """)
    assert out.count("ok") == 4


def test_multipod_mesh_axis():
    """The 'pod' axis shards batches on a (2, 2, 2) toy multi-pod mesh."""
    out = _run("""
        import jax, jax.numpy as jnp
        from repro.distributed import sharding as shd
        mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 3)
        batch = {"tokens": jax.ShapeDtypeStruct((8, 16), jnp.int32)}
        sh = shd.batch_specs(batch, mesh)
        spec = sh["tokens"].spec
        print(spec)
    """)
    assert "pod" in out and "data" in out


def test_pipeline_parallel_ring():
    """4-stage ring pipeline on a 4-device 'stage' mesh: outputs match the
    sequential stack, utilization math holds."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh
        from repro.distributed.pipeline_parallel import pipeline_forward

        mesh = jax.make_mesh((4,), ("stage",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        L, S, mb, d = 8, 4, 2, 16   # 8 layers -> 4 stages x 2 layers
        key = jax.random.key(0)
        w = jax.random.normal(key, (L, d, d)) * 0.1

        def block(params_slice, x):   # params_slice: [2, d, d]
            for i in range(2):
                x = x + jnp.tanh(x @ params_slice[i])
            return x

        x = jax.random.normal(jax.random.key(1), (6, mb, d))  # 6 microbatches
        stage_params = w.reshape(4, 2, d, d)
        got = pipeline_forward(block, stage_params, x, mesh)

        want = x
        for i in range(L):
            want = want + jnp.tanh(want @ w[i])
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)
        print("pipeline ok")
    """)
    assert "pipeline ok" in out
