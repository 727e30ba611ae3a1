"""Serve ResNet-18 at 224x224 on a TPU through the normal path, and check it.

    python3 chip_smoke.py              # one chip
    python3 chip_smoke.py --chips 4    # the four-chip staged path only

One chip: ResNet-18 at its published size (224x224 input, 1000 classes,
weights and frames made from a seed) serves 16 frames through
``CNNApi.serve`` at micro-batch 8, with S=1 and S=2 stages, on two paths:

  * ``xla``    — the executor's default impls (XLA convs and matmuls);
  * ``pallas`` — ``kernel_plan=plan.kernel_plan(batch=8)``: every conv,
    pointwise and dense node runs its planned Pallas kernel, compiled by
    Mosaic (on a TPU nothing is interpreted).

Four chips: S=4 with ``ServeConfig(execute="devices")``, both paths, and
an assertion that the four stages sit on four distinct devices.

Every served output is compared with ``api.apply`` on the XLA path under
``jax.default_matmul_precision("highest")``.  One JSON line per phase
reports the compile and steady seconds, the persistent-cache hits and
misses, the peak device memory and the errors.  Any failure ends the run
non-zero; only a run on a TPU in which every phase passed prints the last
line ``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.

The compile cache is ``JAX_COMPILATION_CACHE_DIR`` where it is set, else
``.jax_cache/`` next to this file.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from fractions import Fraction

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.compile_cache import CacheEvents, use_compile_cache  # noqa: E402
from repro.core.hw_specs import tpu_spec  # noqa: E402
from repro.models.registry import get_cnn_api  # noqa: E402
from repro.serving.config import ServeConfig  # noqa: E402

FAMILY = "resnet18"
INPUT_HW = (224, 224)
CLASSES = 1000
FRAMES = 16
MICROBATCH = 8
RATE = Fraction(3)  # the DSE's input rate (pixels/cycle), as in table4
SEED = 0
PATHS = ("xla", "pallas")
# Scale-relative bound on the logits: max|out - ref| <= TOLERANCE *
# max|ref|.  At default precision an fp32 matmul is one bf16 pass on the
# MXU (8-bit mantissa, about 4e-3 relative rounding per operand); through
# ResNet-18's 21 matmul layers that stays near 1e-2 of the logits'
# scale.  A wrong tap, phase or tile is off by order 1.
TOLERANCE = 5e-2


class SmokeError(RuntimeError):
    pass


def tpu_devices(count: int):
    """The first ``count`` TPU devices and their spec; anything else is an
    error (no CPU fallback, no unknown chip)."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SmokeError(f"no TPU: JAX runs on {devs[0].platform}")
    if len(devs) < count:
        raise SmokeError(f"{count} chips asked for, {len(devs)} found")
    return devs[:count], tpu_spec(devs[0].device_kind)


def build(input_hw=INPUT_HW, num_classes=CLASSES, frames=FRAMES, seed=SEED):
    """ResNet-18 with seeded weights, and ``frames`` seeded frames."""
    api = get_cnn_api(FAMILY)
    cfg = api.make_config(input_hw=input_hw, num_classes=num_classes)
    k_params, k_frames = jax.random.split(jax.random.key(seed))
    params = api.init(cfg, k_params)
    x = np.asarray(
        jax.random.normal(k_frames, (frames, *input_hw, 3), jnp.float32)
    )
    return api, cfg, params, x


def reference(api, cfg, params, x) -> np.ndarray:
    """The XLA path's logits at the highest matmul precision."""
    with jax.default_matmul_precision("highest"):
        fwd = jax.jit(lambda p, x: api.apply(p, x, cfg))
        return np.asarray(fwd(params, x))


def kernel_plan(api, cfg, n_stages, microbatch):
    return api.partition(cfg, RATE, n_stages).kernel_plan(batch=microbatch)


def mosaic_kernels(api, cfg, params, x, plan) -> int:
    """Mosaic kernels in the program ``api.apply`` compiles to for one
    micro-batch ``x`` (with the kernel ``plan``, or the XLA path)."""
    fwd = jax.jit(lambda p, x: api.apply(p, x, cfg, plan=plan))
    return fwd.lower(params, x).compile().as_text().count("tpu_custom_call")


def serve(api, cfg, params, x, *, path, n_stages, execute=True,
          microbatch=MICROBATCH):
    """Serve ``x`` twice through ``api.serve``; the second call reuses the
    compiled stages.  Returns (outputs, first-call s, steady s)."""
    config = ServeConfig(microbatch=microbatch, execute=execute)
    if path == "pallas":
        config = config.with_(
            kernel_plan=kernel_plan(api, cfg, n_stages, microbatch)
        )
    elif path != "xla":
        raise SmokeError(f"unknown path {path!r}")
    seconds, outs = [], []
    for _ in range(2):
        t0 = time.perf_counter()
        out, report = api.serve(
            params, x, cfg, input_rate=RATE, n_stages=n_stages, config=config
        )
        seconds.append(time.perf_counter() - t0)
        if report.completed != len(x):
            raise SmokeError(f"served {report.completed} of {len(x)} frames")
        outs.append(out)
    if not np.array_equal(outs[0], outs[1]):
        raise SmokeError("two serves of the same frames differ")
    return outs[1], seconds[0], seconds[1]


def compare(out, ref) -> dict:
    """Errors of ``out`` against ``ref``; raises beyond ``TOLERANCE``."""
    out = np.asarray(out, np.float64)
    ref = np.asarray(ref, np.float64)
    if out.shape != ref.shape:
        raise SmokeError(f"output shape {out.shape}, reference {ref.shape}")
    if not np.all(np.isfinite(out)):
        raise SmokeError("non-finite outputs")
    max_abs = float(np.max(np.abs(out - ref)))
    max_rel = max_abs / float(np.max(np.abs(ref)))
    top1 = float(np.mean(out.argmax(-1) == ref.argmax(-1)))
    got = {"max_abs_err": max_abs, "max_rel_err": max_rel, "top1_agree": top1}
    if not max_rel <= TOLERANCE:
        raise SmokeError(f"max_rel_err {max_rel} > tolerance {TOLERANCE}: {got}")
    return got


def check_kernels(api, cfg, params, x, emit) -> None:
    """On a TPU the Pallas path compiles to one Mosaic kernel per planned
    node and the XLA path to none: nothing falls back to XLA or to the
    interpreter.  ``x`` is one micro-batch."""
    plan = kernel_plan(api, cfg, 1, len(x))
    planned = sum(node.has_kernel for node in plan.values())
    on_tpu = jax.default_backend() == "tpu"
    for path, want in (("xla", 0), ("pallas", planned)):
        got = mosaic_kernels(
            api, cfg, params, x, plan if path == "pallas" else None
        )
        emit(json.dumps(
            {"phase": "kernels", "path": path, "mosaic_kernels": got}
        ))
        if on_tpu and got != want:
            raise SmokeError(f"{path} path: {got} Mosaic kernels, want {want}")


def stage_devices(api, n_stages: int) -> set:
    """Device tuples of the placed ``n_stages``-stage pipelines that
    ``api.serve`` compiled (its pipeline cache)."""
    return {
        pipe.devices
        for _, pipe in api.caches["pipelines"].values()
        if pipe.n_stages == n_stages and pipe.devices is not None
    }


def peak_bytes(device):
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def run(chips: int, *, input_hw=INPUT_HW, num_classes=CLASSES, frames=FRAMES,
        microbatch=MICROBATCH, emit=print) -> None:
    """Every phase of one smoke run on the devices JAX has."""
    events = CacheEvents()
    t0 = time.perf_counter()
    api, cfg, params, x = build(input_hw, num_classes, frames)
    ref = reference(api, cfg, params, x)
    emit(json.dumps({
        "phase": "reference", "family": FAMILY, "input_hw": list(input_hw),
        "classes": num_classes, "frames": frames,
        "seconds": time.perf_counter() - t0,
    }))
    if chips == 1:
        check_kernels(api, cfg, params, x[:microbatch], emit)
        cells = [(p, s, True) for p in PATHS for s in (1, 2)]
    else:
        cells = [(p, chips, "devices") for p in PATHS]
    for path, n_stages, execute in cells:
        hits, misses = events.snapshot()
        out, first_s, steady_s = serve(
            api, cfg, params, x, path=path, n_stages=n_stages,
            execute=execute, microbatch=microbatch,
        )
        line = {
            "phase": "serve", "path": path, "stages": n_stages,
            "execute": execute, "family": FAMILY, "input_hw": list(input_hw),
            "classes": num_classes, "frames": len(out),
            "microbatch": microbatch, "first_s": first_s,
            "compile_s": first_s - steady_s, "steady_s": steady_s,
            "cache_hits": events.hits - hits,
            "cache_misses": events.misses - misses,
            **compare(out, ref),
        }
        if execute == "devices":
            placed = stage_devices(api, n_stages)
            if not placed or any(len(set(d)) != n_stages for d in placed):
                raise SmokeError(
                    f"{n_stages} stages not on {n_stages} distinct devices: "
                    f"{placed}"
                )
            line["stage_devices"] = sorted(
                [d.id for d in devs] for devs in placed
            )
        line["peak_bytes_in_use"] = peak_bytes(jax.devices()[0])
        emit(json.dumps(line))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    devs, spec = tpu_devices(args.chips)
    cache = use_compile_cache(ROOT)
    print(json.dumps({
        "phase": "device", "platform": devs[0].platform,
        "kind": devs[0].device_kind, "spec": spec.name,
        "vmem_limit_bytes": spec.vmem_bytes, "compile_cache": cache,
    }))
    run(args.chips)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(jax.devices()),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
