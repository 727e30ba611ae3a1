"""Reduction of a profiler trace to the per-layer numbers.

A traced run records the device's operations (the profiler's
``/device:TPU:<n>`` planes, line ``XLA Ops``), and the client records its
own host spans on the profiler's clock (``bench.harness.HostSpans``).
``Reduction`` clips both to the measured window (the host span
``WINDOW``) and gives:

* ``busy_s``: the union of the device's operation intervals, averaged
  over the chips that ran any;
* ``span_idle_s(name)``: per host span of that name, its length less the
  device busy time inside it; ``busy_outside_s(name)``, the busy time
  outside every such span;
* ``kernel_time(kind)`` and ``least_time(kind)``: summed device time of
  one kernel kind's calls, and the least time the chip could take for
  them (``bench/kernels``, ``bench/peaks.json``).  On the TPU an op event
  carries its whole HLO instruction, so a Mosaic call's kind and shapes
  are read from the event itself; ``calls`` may add ones known by name;
* ``top_ops`` and ``idle_gaps``: the breakdown, each idle gap named by
  the host span it falls in.
"""

from __future__ import annotations

import collections
import glob
import os

from bench.kernels import mosaic_calls

WINDOW = "bench_window"
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"


def xplane_path(logdir: str) -> str:
    found = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"), recursive=True)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return max(found, key=os.path.getmtime)


def _stat(ev, key):
    try:
        return dict(ev.stats).get(key)
    except Exception:  # stats of an unreadable type
        return None


def op_name(text: str) -> str:
    """The HLO instruction name of a device op event.  On the TPU the
    event carries the whole instruction (``name = shape op(...), ...``);
    its name is the part before `` = ``."""
    text = text.strip().lstrip("%")
    if text.startswith("ROOT "):
        text = text[5:].lstrip("%")
    return text.split(" = ", 1)[0]


def read_ops(profile) -> list:
    """The device ops of a ``jax.profiler.ProfileData``, each as
    (chip, op text, start ns, end ns)."""
    ops = []
    for plane in profile.planes:
        if not plane.name.startswith(DEVICE_PREFIX):
            continue
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                name = _stat(ev, "hlo_op") or ev.name
                ops.append((plane.name, str(name),
                            float(ev.start_ns), float(ev.end_ns)))
    return ops


def union(intervals):
    """Sorted, merged (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def covered(merged, lo, hi) -> float:
    """Length of [lo, hi] that the merged intervals cover."""
    total = 0.0
    for s, e in merged:
        if e <= lo:
            continue
        if s >= hi:
            break
        total += min(e, hi) - max(s, lo)
    return total


class Reduction:
    def __init__(self, ops, spans, peak, calls=None):
        windows = [s for s in spans if s[0] == WINDOW]
        if len(windows) != 1:
            raise ValueError(f"{len(windows)} {WINDOW} spans in the trace")
        _, self.lo, self.hi, _ = windows[0]
        self.window_s = (self.hi - self.lo) * 1e-9
        self.calls = dict(calls or {})
        self.peak = peak
        self.spans = [s for s in spans if s[0] != WINDOW
                      and s[2] > self.lo and s[1] < self.hi]
        self.ops = []
        for c, text, s, e in ops:
            if e <= self.lo or s >= self.hi:
                continue
            name = op_name(text)
            if name not in self.calls:
                self.calls.update(mosaic_calls(text))
            self.ops.append((c, name, max(s, self.lo), min(e, self.hi)))
        by_chip = collections.defaultdict(list)
        for c, _, s, e in self.ops:
            by_chip[c].append((s, e))
        self.merged = {c: union(iv) for c, iv in by_chip.items()}
        self.chips = len(self.merged)

    @property
    def busy_s(self) -> float:
        if not self.chips:
            return 0.0
        total = sum(covered(m, self.lo, self.hi) for m in self.merged.values())
        return total / self.chips * 1e-9

    def span_idle_s(self, name: str) -> list:
        """Device-idle seconds inside each host span ``name``."""
        out = []
        for n, s, e, _ in self.spans:
            if n != name:
                continue
            busy = sum(covered(m, s, e) for m in self.merged.values())
            busy /= max(self.chips, 1)
            out.append(((e - s) - busy) * 1e-9)
        return out

    def busy_outside_s(self, name: str) -> float:
        """Device busy seconds outside every host span ``name``."""
        inside = union([(s, e) for n, s, e, _ in self.spans if n == name])
        busy = sum(covered(m, self.lo, self.hi) - sum(covered(m, s, e) for s, e in inside)
                   for m in self.merged.values())
        return busy / max(self.chips, 1) * 1e-9

    def _kind_ops(self, kind):
        for _, name, s, e in self.ops:
            call = self.calls.get(name)
            if call is not None and call.kind == kind:
                yield call, (e - s) * 1e-9

    def kernel_time(self, kind: str) -> float:
        return sum(t for _, t in self._kind_ops(kind))

    def least_time(self, kind: str) -> tuple:
        """(least seconds, seconds bound by compute, by memory) of the
        kind's calls in the window."""
        least = compute = memory = 0.0
        for call, _ in self._kind_ops(kind):
            tc = call.flops / self.peak["flops"]
            tm = call.bytes / self.peak["hbm_bytes_per_s"]
            least += max(tc, tm)
            if tc >= tm:
                compute += max(tc, tm)
            else:
                memory += max(tc, tm)
        return least, compute, memory

    def top_ops(self, n: int = 10) -> list:
        total = collections.Counter()
        for _, name, s, e in self.ops:
            call = self.calls.get(name)
            label = f"{call.kind}:{name}" if call is not None else name
            total[label] += (e - s) * 1e-9
        return [[k, v] for k, v in total.most_common(n)]

    def idle_gaps(self, n: int = 10) -> list:
        """The ``n`` longest device-idle gaps of the first chip, each
        named by the innermost host span at its midpoint."""
        if not self.merged:
            return [["no device ops", self.window_s]]
        merged = self.merged[sorted(self.merged)[0]]
        edges = [self.lo] + [x for iv in merged for x in iv] + [self.hi]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for s, e in gaps[:n]:
            mid = (s + e) / 2
            inside = [sp for sp in self.spans if sp[1] <= mid <= sp[2]]
            name = min(inside, key=lambda sp: sp[2] - sp[1])[0] if inside else "outside spans"
            out.append([name, (e - s) * 1e-9])
        return out
