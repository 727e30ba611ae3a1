"""Benchmark aggregator: one module per paper table / deliverable.

Prints ``name,us_per_call,derived`` CSV.  Modules:
  table1_mnv1_resources — paper Table I (MNv1 ours vs [11])
  table2_mnv2_rates     — paper Table II (MNv2 across 7 data rates)
  table3_dag_buffers    — DAG skew FIFOs + DAG DSE (MNv2 + ResNet-18)
  table4_resnet_e2e     — CNN E2E inference vs the analytic DSE for all
                          four families, incl. uniform-vs-rate-matched
                          Pallas tiling GMAC/s and a batch sweep
  table5_partition      — multi-chip DAG stage partitioning: bottleneck,
                          balance, cut-crossing stream buffers, chain-DP
                          baseline for all four families at S in {2,3,4}
  table6_serving        — streaming serving engine: BestRate admission,
                          throughput/latency ticks, occupancy vs the
                          analytical bound, queue depth vs caps across
                          an arrival sweep (deterministic tick model)
  table7_fleet          — Multi-CLP bottleneck replication (strict
                          stage-balance win at equal arithmetic) + the
                          multi-tenant chip-pool planner and shared-clock
                          fleet scheduler (deterministic models)
  table8_overload       — overload-resilient serving: bursty/diurnal/
                          adversarial traffic x {baseline, SLA shed,
                          plan switch} with p99 growth verdicts
                          (deterministic tick model)
  table9_memory         — memory-efficient streams: fp32-vs-int8 cut
                          crossing bits (the 4x wire narrowing) and
                          bram_budget-constrained fallback cuts for all
                          four families at S in {2,3}
  table10_wallclock     — wall-clock multi-device staged execution:
                          GPipe placement ordinals + utilization bounds
                          (pinned) and measured fps / overlap speedup
                          (excluded from gating — timing, not structure)
  table11_observability — rate-calculus observability: the drift
                          auditor reproduces the engine's occupancy/
                          queue/stall verdicts from the recorded trace
                          alone, localizes the first stall tick of the
                          table8 adversarial overload, and the trace-off
                          run stays byte-identical (deterministic tick
                          model — all rows pinned)
  rate_aware_serving    — the technique applied to LM serving (DESIGN §3)
  kernel_bench          — Pallas kernels vs oracles + tile stats
  roofline              — 40-cell roofline summary (needs dry-run JSONs)

``--only a,b,c`` restricts to named modules (CI smoke uses the analytic
tables, which need no accelerator and finish in seconds); names are
case/whitespace-normalized and unknown names are an error.  ``--json F``
additionally writes the rows to F for the bench-regression CI gate
(benchmarks/check_regression.py compares the ``derived`` column of the
analytic tables against benchmarks/baselines/).

Compiled programs go to JAX's persistent cache: ``JAX_COMPILATION_CACHE_DIR``
where it is set, else ``.jax_cache/`` at the repo root
(``repro.compile_cache``).
"""
from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import traceback

# name -> module path; imported lazily so a restricted `--only` run never
# pays for (or breaks on) imports it does not use
MODULES = [
    ("table1", "benchmarks.table1_mnv1_resources"),
    ("table2", "benchmarks.table2_mnv2_rates"),
    ("table3", "benchmarks.table3_dag_buffers"),
    ("table4", "benchmarks.table4_resnet_e2e"),
    ("table5", "benchmarks.table5_partition"),
    ("table6", "benchmarks.table6_serving"),
    ("table7", "benchmarks.table7_fleet"),
    ("table8", "benchmarks.table8_overload"),
    ("table9", "benchmarks.table9_memory"),
    ("table10", "benchmarks.table10_wallclock"),
    ("table11", "benchmarks.table11_observability"),
    ("rate_aware", "benchmarks.rate_aware_serving"),
    ("kernels", "benchmarks.kernel_bench"),
    ("roofline", "benchmarks.roofline"),
]


def parse_only(only: str) -> set:
    """Normalize a ``--only`` value: case-insensitive, whitespace-tolerant.

    Raises SystemExit on names that match no module (a bare/typoed value
    must fail loudly, not silently run nothing).
    """
    selected = {m.strip().lower() for m in only.split(",")}
    selected.discard("")
    if not selected:
        raise SystemExit(
            "--only given but no module names parsed (got "
            f"{only!r})")
    known = {name for name, _ in MODULES}
    unknown = selected - known
    if unknown:
        raise SystemExit(
            f"unknown benchmark modules: {sorted(unknown)} "
            f"(known: {sorted(known)})")
    return selected


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--only", default="",
                    help="comma-separated module names (default: all)")
    ap.add_argument("--json", default="", metavar="FILE",
                    help="also write rows as JSON (for check_regression.py)")
    args = ap.parse_args(argv)
    from repro.compile_cache import use_compile_cache

    use_compile_cache(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    mods = MODULES
    if args.only.strip():
        selected = parse_only(args.only)
        mods = [(n, m) for n, m in mods if n in selected]

    failures = 0
    rows = []
    for name, path in mods:
        try:
            mod = importlib.import_module(path)
            for row, us, derived in mod.run():
                rows.append({"name": row, "us": us, "derived": derived})
                print(f"{row},{us:.1f},{derived}")
        except Exception:
            failures += 1
            print(f"{name},0.0,ERROR", file=sys.stderr)
            traceback.print_exc()
    if args.json:
        with open(args.json, "w") as f:
            json.dump(rows, f, indent=1)
        print(f"wrote {len(rows)} rows to {args.json}", file=sys.stderr)
    if failures:
        raise SystemExit(f"{failures} benchmark modules failed")


if __name__ == "__main__":
    main()
