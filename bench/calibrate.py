"""Readings that the ``correct`` limits are set from (run on the chip).

    python3 bench/calibrate.py --workload resnet18.saturate --seeds 1 12

For each seed: the cell's weights and frame pool, every pool frame served
through the timed path at the cell's own sizes (``Served.serve``, calls
of the mix's frames per call), and the compared numbers
(``bench/check.py``): the worst frame's error, and ``_mean``, the mean
over the frames, of

* ``program``: the served logits, at the configuration's matmul
  precision, against the reference at its ``reference`` numerics — its
  largest over a dozen seeds or more is the limit's lower reading;
* the configuration's ``control``: the reference computed one precision
  step below the configuration's (``high``, three passes, for float32 at
  ``highest``) — its smallest over the seeds is the upper reading;
* the reference's other numerics (``bench/reference/ops.NUMERICS``), and
  ``xla_default``, float32 at XLA's default matmul precision;

each against the reference at ``highest`` and at ``one_pass``.

One JSON line per seed, then a summary line.  The benchmark's own runs
never run this.
"""

import argparse
import functools
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from bench import check, harness, traffic  # noqa: E402
from bench.reference import ops  # noqa: E402


def forward_in_blocks(served, num):
    c = served.cell.config
    fwd = jax.jit(functools.partial(served.reference.forward, cfg=c, num=num))
    out = []
    for s in range(0, len(served.pool), harness.REF_BLOCK):
        block = served.pool[s:s + harness.REF_BLOCK]
        out.append(np.asarray(fwd(served.params, block), np.float32))
    return np.concatenate(out)


XLA_DEFAULT = ops.Numerics(jnp.float32, jnp.float32, jax.lax.Precision.DEFAULT)


def readings(served, seed):
    """``logit_err`` of the program, of each reference numerics and of
    XLA's default precision, against each reference numerics."""
    c, mix = served.cell.config, served.cell.mix
    served.pool = traffic.pool(seed, mix["pool_frames"], c["input_hw"],
                               c["channels"])
    served.params = harness.make_params(served.reference, c, seed)
    per = mix.get("frames_per_call") or mix["max_frames_per_call"]
    idx = np.arange(len(served.pool))
    runs = {"program": np.concatenate(
        [served.serve(list(served.pool[idx[s:s + per]]))
         for s in range(0, len(idx), per)])}
    for name, num in (*ops.NUMERICS.items(), ("xla_default", XLA_DEFAULT)):
        runs[name] = forward_in_blocks(served, num)
    out = {}
    for b in ("highest", "one_pass"):
        for a in runs:
            if a != b:
                err = check.frame_errors(runs[a], runs[b])
                out[f"{a}_vs_{b}"] = float(err.max())
                out[f"{a}_vs_{b}_mean"] = float(err.mean())
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs=2, metavar=("FIRST", "LAST"),
                    required=True)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    devices, _ = harness.tpu_devices(cell.workload["chips"])
    harness.use_compile_cache()
    served = harness.setup(cell, args.seeds[0])
    rows = []
    for seed in range(args.seeds[0], args.seeds[1] + 1):
        row = {"seed": seed, **readings(served, seed)}
        rows.append(row)
        print(json.dumps(row), flush=True)
    c = cell.config
    ref, ctl, limits = c["reference"], c["control"], c["limits"]
    summary = {"workload": args.workload, "seeds": len(rows),
               "device": devices[0].device_kind, "reference": ref, "control": ctl}
    for number, suffix in (("logit_err", ""), ("logit_err_mean", "_mean")):
        summary[number] = {
            "program_max": max(r[f"program_vs_{ref}{suffix}"] for r in rows),
            "control_min": min(r[f"{ctl}_vs_{ref}{suffix}"] for r in rows),
            "limit": limits[number]}
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
