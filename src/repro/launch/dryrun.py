import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

THE FIRST TWO LINES of this file force 512 host platform devices BEFORE
any jax import — jax locks device count on first init.  Do not move them.

For every enabled cell this driver:
  1. builds the production mesh (single-pod 16x16 or multi-pod 2x16x16);
  2. builds abstract, sharding-annotated inputs (ShapeDtypeStructs — no
     allocation);
  3. jit-lowers + compiles the step (train_step for train shapes,
     prefill/decode for serve shapes);
  4. records memory_analysis (proves it fits 16 GB/chip),
     cost_analysis (FLOPs/bytes) and the collective bytes parsed from the
     compiled per-device HLO — the three roofline terms —
     into results/dryrun/<arch>__<shape>__<mesh>.json.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-7b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--skip-done]
"""
import argparse
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, active_param_count, param_count
from repro.configs.registry import ARCHS, get_config
from repro.configs.shapes import SHAPES, ShapeSuite, cell_enabled, skip_reason
from repro.core.flops import scan_trips, step_flops, step_hbm_bytes
from repro.core.hlo_analysis import (collective_bytes, normalize_cost_analysis,
                                     roofline_terms)
from repro.distributed import sharding as shd
from repro.launch.mesh import make_production_mesh
from repro.launch.train import adam_config_for, build_train_step
from repro.models import lm_api
from repro.optim import optimizers as opt


def _tokens_per_step(cfg: ModelConfig, shape: ShapeSuite) -> int:
    if shape.kind == "train":
        return shape.global_batch * shape.seq_len
    if shape.kind == "prefill":
        return shape.global_batch * shape.seq_len
    return shape.global_batch  # decode: 1 token per sequence


def model_flops(cfg: ModelConfig, shape: ShapeSuite) -> float:
    """6*N*D train / 2*N*D serve (N = active params for MoE)."""
    n = active_param_count(cfg)
    d = _tokens_per_step(cfg, shape)
    mult = 6.0 if shape.kind == "train" else 2.0
    return mult * n * d


def build_cell(cfg: ModelConfig, shape: ShapeSuite, mesh):
    """-> (fn, abstract_args): the jit-able step + sharded abstract args."""
    api = lm_api.get_api(cfg)

    if shape.kind == "train":
        adam = adam_config_for(cfg)
        p_abs = jax.eval_shape(lambda: api.init(cfg, jax.random.key(0)))
        o_abs = jax.eval_shape(lambda: opt.init(adam, p_abs))
        b_abs = lm_api.train_batch_specs(cfg, shape)
        p_sh = shd.params_shardings(p_abs, mesh)
        step = build_train_step(cfg, adam, grad_shardings=p_sh)
        o_sh = shd.opt_state_shardings(o_abs, p_abs, mesh)
        b_sh = shd.batch_specs(b_abs, mesh)
        args = (shd.abstract_with_shardings(p_abs, p_sh),
                shd.abstract_with_shardings(o_abs, o_sh),
                shd.abstract_with_shardings(b_abs, b_sh))
        return step, args

    if cfg.serve_weight_quant:
        from repro.nn.quant import quantize_tree
        p_abs = jax.eval_shape(
            lambda: quantize_tree(api.init(cfg, jax.random.key(0))))
    else:
        p_abs = jax.eval_shape(lambda: api.init(cfg, jax.random.key(0)))
    p_sh = shd.params_shardings(p_abs, mesh)
    p_in = shd.abstract_with_shardings(p_abs, p_sh)
    st_abs = lm_api.serve_state_specs(cfg, shape)
    st_sh = shd.serve_state_specs(st_abs, mesh)
    st_in = shd.abstract_with_shardings(st_abs, st_sh)

    if shape.kind == "prefill":
        b_abs = lm_api.prefill_batch_specs(cfg, shape)
        b_in = shd.abstract_with_shardings(b_abs, shd.batch_specs(b_abs, mesh))

        def prefill_step(params, batch, state):
            return api.prefill(params, batch, state, cfg)

        return prefill_step, (p_in, b_in, st_in)

    # decode
    b_abs = lm_api.decode_batch_specs(cfg, shape)
    b_in = shd.abstract_with_shardings(b_abs, shd.batch_specs(b_abs, mesh))
    pos = jax.ShapeDtypeStruct((), jnp.int32)

    def decode_step(params, state, batch, pos):
        return api.decode(params, state, batch, pos, cfg)

    return decode_step, (p_in, st_in, b_in, pos)


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             out_dir: Path) -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    record = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "params": param_count(cfg), "active_params": active_param_count(cfg),
        "model_flops": model_flops(cfg, shape),
        "tokens_per_step": _tokens_per_step(cfg, shape),
    }
    if not cell_enabled(cfg, shape):
        record.update(status="skipped", reason=skip_reason(cfg, shape))
        return record

    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    fn, args = build_cell(cfg, shape, mesh)

    # donate the state-like args (params+opt for train, caches for serve)
    # so memory_analysis reflects steady-state buffers, as the real loop
    # runs them.
    donate = {"train": (0, 1), "prefill": (2,), "decode": (1,)}[shape.kind]
    t0 = time.perf_counter()
    with mesh:
        lowered = jax.jit(fn, donate_argnums=donate).lower(*args)
        t_lower = time.perf_counter() - t0
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0 - t_lower
        mem = compiled.memory_analysis()
        cost = normalize_cost_analysis(compiled.cost_analysis())
        hlo = compiled.as_text()

    trips = scan_trips(cfg, shape)
    colls = collective_bytes(hlo, loop_trips=trips)
    # XLA cost_analysis counts scan bodies ONCE (layer stacks + microbatch
    # accumulation are scanned) -> correct FLOPs analytically
    # (core/flops.py, validated vs unrolled compiles in tests) and scale
    # bytes by the same trip ratio.  Raw numbers are recorded alongside.
    raw_flops = float(cost.get("flops", 0.0))
    raw_bytes = float(cost.get("bytes accessed", 0.0))
    ana_flops_per_dev = step_flops(cfg, shape) / chips
    trip_ratio = (ana_flops_per_dev / raw_flops) if raw_flops else 1.0
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n_model = sizes.get("model", 1)
    n_data = sizes.get("data", 1) * sizes.get("pod", 1)
    ana_bytes_per_dev = step_hbm_bytes(cfg, shape, n_model, n_data)
    corr_bytes = max(raw_bytes, ana_bytes_per_dev)
    terms = roofline_terms(cost, hlo, chips,
                           model_flops=record["model_flops"],
                           flops_override=ana_flops_per_dev,
                           bytes_override=corr_bytes,
                           loop_trips=trips)
    per_dev_raw = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                   - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    # The CPU backend emulates bf16 arithmetic by converting temporaries
    # to f32 (verified: convert->f32 chains on cache/dispatch buffers in
    # the compiled HLO).  Interface buffers (args/outputs) keep their real
    # dtypes; temps for bf16 models are ~2x inflated vs a TPU build.  The
    # fit check therefore uses the bf16-native estimate; both recorded.
    temp_factor = 0.5 if cfg.param_dtype == "bfloat16" else 1.0
    per_dev_native = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                      - mem.alias_size_in_bytes
                      + int(mem.temp_size_in_bytes * temp_factor))
    record.update(
        status="ok",
        chips=chips,
        lower_s=round(t_lower, 2),
        compile_s=round(t_compile, 2),
        memory={
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "per_device_total_raw_cpu": per_dev_raw,
            "per_device_total": per_dev_native,
            "cpu_bf16_temp_factor": temp_factor,
            "fits_16GiB": bool(per_dev_native <= 16 * 1024 ** 3),
        },
        cost={k: cost.get(k) for k in ("flops", "bytes accessed",
                                       "transcendentals") if k in cost},
        flops_correction={
            "raw_hlo_flops_per_dev": raw_flops,
            "analytic_flops_per_dev": ana_flops_per_dev,
            "scan_trip_ratio": round(trip_ratio, 3),
            "corrected_bytes_per_dev": corr_bytes,
        },
        collectives={
            "bytes_by_kind": colls.bytes_by_kind,
            "count_by_kind": colls.count_by_kind,
            "total_bytes": colls.total_bytes,
        },
        roofline=terms.summary(),
    )
    return record


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHS), default=None)
    ap.add_argument("--shape", choices=sorted(SHAPES), default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--skip-done", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    args = ap.parse_args()

    out_dir = Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)

    cells = []
    archs = sorted(ARCHS) if (args.all or not args.arch) else [args.arch]
    shapes = sorted(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    for a in archs:
        for s in shapes:
            for mp in meshes:
                cells.append((a, s, mp))

    failures = 0
    for arch, shape, mp in cells:
        mesh_name = "pod2x16x16" if mp else "pod16x16"
        path = out_dir / f"{arch}__{shape}__{mesh_name}.json"
        if args.skip_done and path.exists():
            prev = json.loads(path.read_text())
            if prev.get("status") in ("ok", "skipped"):
                print(f"[dryrun] {arch} x {shape} x {mesh_name}: cached")
                continue
        print(f"[dryrun] {arch} x {shape} x {mesh_name} ...", flush=True)
        try:
            rec = run_cell(arch, shape, multi_pod=mp, out_dir=out_dir)
        except Exception as e:  # record the failure, keep sweeping
            rec = {"arch": arch, "shape": shape, "mesh": mesh_name,
                   "status": "error", "error": repr(e),
                   "traceback": traceback.format_exc()[-4000:]}
            failures += 1
        path.write_text(json.dumps(rec, indent=1))
        if rec["status"] == "ok":
            r = rec["roofline"]
            print(f"  ok: compile {rec['compile_s']}s | "
                  f"mem/dev {rec['memory']['per_device_total'] / 2**30:.2f} GiB "
                  f"fits={rec['memory']['fits_16GiB']} | "
                  f"compute {r['compute_s']:.3e}s mem {r['memory_s']:.3e}s "
                  f"coll {r['collective_s']:.3e}s -> {r['dominant']}",
                  flush=True)
            print(compiled_summary(rec), flush=True)
        elif rec["status"] == "skipped":
            print(f"  skipped: {rec['reason']}")
        else:
            print(f"  ERROR: {rec['error']}")
    if failures:
        raise SystemExit(f"{failures} cells failed")


def compiled_summary(rec: dict) -> str:
    r = rec["roofline"]
    return (f"  roofline_fraction={r['roofline_fraction']:.3f} "
            f"useful_flops={r['useful_flops_ratio']:.3f}")


if __name__ == "__main__":
    main()
