"""Sweep the offered rate of a camera mix to find its knee (run on the
chip).

    python3 bench/sweep.py --workload resnet18.stream --seed 1 \\
        --seconds 8 --cameras 16 24 32 40 48

One set-up, then for each camera count one window of the cell's traffic
with only ``cameras`` changed: offered and completed frames per second,
the p50 and p99 latency and the frames still due after the window, one
JSON line each.  The knee is the highest rate whose completed rate keeps
up with the offered one and whose p99 stays within a few calls; the cell
is set at about four fifths of it.  The benchmark's own runs never run
this.
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import numpy as np  # noqa: E402

from bench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--cameras", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    if cell.mix["kind"] != "cameras":
        raise SystemExit(f"{args.workload}: not a camera mix")
    harness.tpu_devices(cell.workload["chips"])
    harness.use_compile_cache()
    served = harness.setup(cell, args.seed)
    for cams in args.cameras:
        cell.mix["cameras"] = cams
        w = harness.measure(served, args.seed, args.seconds)
        lat = 1e3 * (w.returned - w.arrival)
        late = np.isfinite(lat) & (w.returned > args.seconds)
        print(json.dumps({
            "cameras": cams,
            "offered_per_s": cams * cell.mix["fps"],
            "completed_per_s": w.due / w.seconds if w.seconds else 0.0,
            "p50_ms": float(np.percentile(lat, 50)),
            "p99_ms": float(np.percentile(lat, 99)),
            "max_ms": float(np.max(lat)),
            "mean_frames_per_call": w.due / max(len(w.calls), 1),
            "returned_after_window": int(late.sum()),
            "lowered": w.lowered,
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
