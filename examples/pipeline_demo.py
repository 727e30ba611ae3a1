"""Pipeline parallelism with rate-aware stage balance — live demo.

Runs a toy residual-block stack through the ring pipeline
(`distributed/pipeline_parallel.py`) on virtual devices and shows the
paper's continuous-flow math at stage level:

  1. Uneven per-layer costs (a 'pooling-like' cost drop mid-network) get
     partitioned by the min-bottleneck DP (`core.stage_partition`) —
     compare against the naive equal-layer-count split.
  2. The GPipe bubble follows util = M/(M+S-1): measured step counts
     match the formula.
  3. Numerics: pipeline output == sequential stack output exactly.
  4. Wall-clock: a real staged CNN on the same 4 virtual devices via
     `distributed/device_pipeline.py` — stage s of the cut plan lands on
     device s, micro-batches overlap under the GPipe schedule, and the
     report shows measured frames/sec against the sequential baseline.

This is a CPU virtual-device demo: it re-executes itself as a child
pinned to ``JAX_PLATFORMS=cpu`` with XLA_FLAGS for 4 virtual CPU
devices, and the parent never imports JAX (so on a machine with an
accelerator the child cannot collide with a process holding the chip).

Run: PYTHONPATH=src python examples/pipeline_demo.py
"""
import os
import subprocess
import sys


def _main() -> None:
    import jax
    import jax.numpy as jnp

    from repro.core.stage_partition import (partition_min_bottleneck,
                                            service_rates)
    from repro.distributed.pipeline_parallel import (
        microbatch_utilization, pipeline_forward)

    print("=== 1. rate-aware stage partition ===")
    # 16 layers; the back half is 4x cheaper (post-'pooling' rate drop)
    costs = [4.0] * 8 + [1.0] * 8
    plan = partition_min_bottleneck(costs, 4)
    naive = max(sum(costs[i * 4:(i + 1) * 4]) for i in range(4))
    print(f"  per-layer costs: {costs}")
    print(f"  DP stage bounds: {plan.boundaries}  "
          f"(stage costs {plan.stage_cost})")
    print(f"  bottleneck {plan.bottleneck} vs naive equal-count {naive} "
          f"-> {naive / plan.bottleneck:.2f}x more throughput")

    print("\n=== 2. pipeline ring on 4 devices ===")
    mesh = jax.make_mesh(
        (4,), ("stage",), axis_types=(jax.sharding.AxisType.Auto,))
    L, mb, d, M = 8, 4, 32, 12
    w = jax.random.normal(jax.random.key(0), (L, d, d)) * 0.1

    def block(ps, x):
        for i in range(ps.shape[0]):
            x = x + jnp.tanh(x @ ps[i])
        return x

    x = jax.random.normal(jax.random.key(1), (M, mb, d))
    got = pipeline_forward(block, w.reshape(4, 2, d, d), x, mesh)
    want = x
    for i in range(L):
        want = want + jnp.tanh(want @ w[i])
    err = float(jnp.max(jnp.abs(got - want)))
    print(f"  {M} microbatches x 4 stages: max |pipeline - sequential| "
          f"= {err:.2e}")
    assert err < 1e-4
    print(f"  utilization (GPipe bubble): {microbatch_utilization(M, 4):.3f} "
          f"(= M/(M+S-1) = {M}/{M + 3})")

    print("\n=== 3. chips-per-stage for heterogeneous stages ===")
    from repro.core.stage_partition import allocate_chips
    chips = allocate_chips(list(plan.stage_cost), 16)
    rates = service_rates(list(plan.stage_cost), chips, 1.0)
    print(f"  16 chips over stages {plan.stage_cost} -> {chips} "
          f"(min service rate {min(rates):.3f}/s vs even-split "
          f"{min(service_rates(list(plan.stage_cost), [4] * 4, 1.0)):.3f}/s)")
    print("\n=== 4. wall-clock staged CNN on the 4 virtual CPU devices ===")
    from fractions import Fraction as F

    import numpy as np

    from repro.distributed.device_pipeline import DevicePipeline
    from repro.models.registry import get_cnn_api

    api = get_cnn_api("resnet18")
    cfg = api.make_config(input_hw=(32, 32), num_classes=10)
    params = api.init(cfg, jax.random.PRNGKey(0))
    cut = api.partition(cfg, F(3), 4)
    frames = np.asarray(
        jax.random.normal(jax.random.key(2), (8, 32, 32, 3)), np.float32)
    dp = DevicePipeline.build(
        api.graph(cfg), params, partition=cut, placement=True)
    print(f"  resnet18 cut into {dp.n_stages} stages on devices "
          f"{dp.placement_ordinals()}")
    rep = dp.measure(frames, microbatch=1, warmup=1, repeats=2)
    print(f"  overlap {rep.fps_overlap:8.1f} frames/s   "
          f"sequential {rep.fps_sequential:8.1f} frames/s   "
          f"speedup {rep.speedup:.2f}x (bound {rep.utilization_bound:.2f})")
    print("  stage busy fractions: "
          + ", ".join(f"s{i}={f:.2f}" for i, f in
                      enumerate(rep.stage_busy_frac)))

    print("\nContinuous flow at rack scale: every stage's service rate "
          "covers the stream — the paper's j/h >= r, in chips.")


if __name__ == "__main__":
    if os.environ.get("_PIPE_DEMO_CHILD") != "1":
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        env["_PIPE_DEMO_CHILD"] = "1"
        raise SystemExit(subprocess.call([sys.executable, __file__], env=env))
    _main()
