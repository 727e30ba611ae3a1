"""The comparison that decides ``correct``.

Every frame whose logits came back to the client in the window is
compared with the plain reference of its own pool frame
(``bench/reference``, at the numerics the configuration's ``reference``
names: ``highest`` for a model served in float32 at the highest matmul
precision).  A frame's error is its largest logit error over its largest
reference logit: max_j |served_j - ref_j| / max_j |ref_j|.  Two numbers
are compared:

* ``logit_err_mean``, the mean of the frames' errors: it tells the
  program's precision from one step lower (the configuration's
  ``control``);
* ``logit_err``, the worst frame's error, against a looser limit: one
  answer altered, swapped or taken from another frame.

A frame due in the window that never came back is ``missing``; its
limit is 0.  The limits are the configuration's (``bench/configs``); the
readings they were set from are in ``PERF.md``.
"""

from __future__ import annotations

import numpy as np


def frame_errors(out: np.ndarray, ref: np.ndarray) -> np.ndarray:
    """Per frame: max |out - ref| / max |ref|, in float64; inf where the
    served logits are not finite."""
    out = np.asarray(out, np.float64)
    ref = np.asarray(ref, np.float64)
    err = np.max(np.abs(out - ref), axis=-1) / np.max(np.abs(ref), axis=-1)
    return np.where(np.all(np.isfinite(out), axis=-1), err, np.inf)


def _number(value: float, limit: float) -> dict:
    return {"value": value if np.isfinite(value) else None, "limit": limit}


def compare(served, ref_logits, limits: dict, due: int) -> dict:
    """``served``: (pool indices, logits) per call; ``ref_logits``: the
    reference's logits per pool index; ``due``: frames due in the window.
    Returns the verdict with each number beside its limit."""
    errs, returned = [], 0
    for idx, out in served:
        returned += len(idx)
        if out.shape != (len(idx), ref_logits.shape[-1]):
            errs.append(np.full(len(idx), np.inf))
            continue
        errs.append(frame_errors(out, ref_logits[idx]))
    errs = np.concatenate(errs) if errs else np.array([np.inf])
    worst, mean = float(errs.max()), float(errs.mean())
    wrong = int(np.sum(~(errs <= limits["logit_err"])))
    missing = due - returned
    numbers = {
        "logit_err_mean": _number(mean, limits["logit_err_mean"]),
        "logit_err": _number(worst, limits["logit_err"]),
        "missing": {"value": missing, "limit": 0},
    }
    correct = (returned > 0 and missing == 0 and mean <= limits["logit_err_mean"]
               and worst <= limits["logit_err"])
    return {"correct": bool(correct), "attempted": due,
            "failed": max(missing, 0) + wrong, "check": numbers}
