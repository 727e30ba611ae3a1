"""Set-up, measured window, reference and result of one benchmark run.

``bench/run.py`` is the command; this module is what it runs, split so
that the tests can drive a run at a small size on the CPU:

    cell = load_cell("resnet18.saturate")
    served = setup(cell, seed)              # weights, frames, plan, warm-up
    window = measure(served, seed, seconds) # the timed client loop
    verdict = verify(served, window)        # the plain reference

The served path is ``CNNApi.serve(params, frames, cfg, input_rate=R,
n_stages=S, config=ServeConfig(microbatch=B, kernel_plan=K))``, with
``K = api.partition(cfg, R, S).kernel_plan(batch=B)``: the rate-matched
per-node Pallas plan that ``rate_matched=True`` lowers, built once in
set-up instead of once per call.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import json
import os
import sys
import time
from fractions import Fraction
import jax
import jax.numpy as jnp
import numpy as np

from bench import check, traffic
from bench.reference import load as load_reference, ops

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
REF_BLOCK = 32       # frames per reference call
DRAIN_S = 60.0       # how long frames due in the window may take after it
TRACE_SECONDS = 3.0  # length of a traced window


class BenchError(RuntimeError):
    pass


def _json(path):
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    workload: dict
    config: dict
    mix: dict
    bench: dict

    def metrics(self, section: str) -> list:
        """The ``section`` metrics of ``BENCHMARK.json`` this cell reports."""
        return [m for m in self.bench[section]
                if self.name in m.get("workloads", [self.name])]


def load_cell(name: str) -> Cell:
    bench = _json(os.path.join(ROOT, "BENCHMARK.json"))
    found = [w for w in bench["workloads"] if w["name"] == name]
    if not found:
        known = ", ".join(w["name"] for w in bench["workloads"])
        raise BenchError(f"unknown workload {name!r}; known: {known}")
    wl = found[0]
    config = _json(os.path.join(HERE, "configs", f"{wl['config']}.json"))
    return Cell(name, wl, config, traffic.load(wl["traffic"]), bench)


def seed_key(seed: int):
    """A PRNG key for any whole seed (wider than 32 bits too)."""
    seed %= 2**64
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def tpu_devices(chips: int):
    """The chips, and the peaks of their kind; no accelerator, too few
    chips or a kind without peaks is an error."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX runs on {devs[0].platform}")
    if len(devs) < chips:
        raise BenchError(f"{chips} chips asked for, {len(devs)} found")
    return devs[:chips], peak_of(devs[0].device_kind)


def peak_of(kind: str) -> dict:
    table = _json(os.path.join(HERE, "peaks.json"))
    if kind not in table or kind == "source":
        raise BenchError(f"no peaks for device kind {kind!r} in bench/peaks.json")
    return table[kind]


def use_compile_cache() -> str:
    """JAX's persistent cache: ``JAX_COMPILATION_CACHE_DIR`` where set,
    else the fixed ``.jax_cache/`` in the checkout.  Every program is
    cached, however fast it compiled, so that set-up stays the same from
    the second run on."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class _LoweringCount:
    """Programs lowered while ``on``: each is then compiled or loaded from
    the persistent cache.  The window should lower none."""

    def __init__(self):
        self.n = 0
        self.on = False
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event, *_, **__):
        if self.on and event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            self.n += 1


@dataclasses.dataclass
class Served:
    cell: Cell
    api: object
    model_cfg: object
    params: dict
    serve_config: object
    pool: np.ndarray
    reference: object
    flops_per_frame: int
    setup_split: dict
    lowered: _LoweringCount = dataclasses.field(default_factory=_LoweringCount)

    def precision(self):
        """The configuration's matmul precision, under which every stage
        is traced, compiled and run."""
        return jax.default_matmul_precision(self.cell.config["matmul_precision"])

    def serve(self, frames):
        """One call of the served path; returns the logits on the host."""
        c = self.cell.config
        with self.precision():
            out, report = self.api.serve(
                self.params, frames, self.model_cfg,
                input_rate=Fraction(c["input_rate"]), n_stages=c["n_stages"],
                config=self.serve_config,
            )
        if report.completed != len(frames):
            raise BenchError(f"served {report.completed} of {len(frames)} frames")
        return out


def make_params(ref, config: dict, seed: int) -> dict:
    """The weights, on the device in one jitted call from the seed."""
    init = jax.jit(functools.partial(ops.init, ref.layers(config)))
    return jax.block_until_ready(init(seed_key(seed)))


def setup(cell: Cell, seed: int) -> Served:
    """Weights on the device (one jitted call), the frame pool on the
    host, the kernel plan, and a warm-up of the one shape per stage."""
    from repro.models.registry import get_cnn_api
    from repro.serving.config import ServeConfig

    c, split = cell.config, {}
    ref = load_reference(c["family"])
    t = time.perf_counter()
    pool = traffic.pool(seed, cell.mix["pool_frames"], c["input_hw"], c["channels"])
    split["pool_s"] = time.perf_counter() - t

    t = time.perf_counter()
    params = make_params(ref, c, seed)
    split["params_s"] = time.perf_counter() - t

    t = time.perf_counter()
    api = get_cnn_api(c["family"])
    model_cfg = api.make_config(input_hw=tuple(c["input_hw"]),
                                num_classes=c["num_classes"],
                                dtype=jnp.dtype(c["dtype"]))
    plan = api.partition(model_cfg, Fraction(c["input_rate"]), c["n_stages"])
    serve_config = ServeConfig(
        microbatch=c["microbatch"],
        kernel_plan=plan.kernel_plan(batch=c["microbatch"]),
    )
    split["plan_s"] = time.perf_counter() - t

    served = Served(cell, api, model_cfg, params, serve_config, pool, ref,
                    2 * ops.macs(ref.layers(c)), split)
    t = time.perf_counter()
    # the first call compiles (or loads) every stage; a partial and a
    # full chunk then cover the padded micro-batch and the host path
    per_call = cell.mix.get("frames_per_call") or cell.mix["max_frames_per_call"]
    for n in (c["microbatch"] + 1, per_call):
        served.serve(list(pool[:n]))
    split["warmup_s"] = time.perf_counter() - t
    return served


@dataclasses.dataclass
class Window:
    """What the client saw: per call the pool indices and logits, and
    per frame the scheduled arrival and the return, in seconds from the
    window's start."""

    calls: list
    arrival: np.ndarray
    returned: np.ndarray
    seconds: float
    due: int
    lowered: int  # programs lowered inside the window
    start: float  # perf_counter at the first timed arrival


def measure(served: Served, seed: int, seconds: float, *,
            span=contextlib.nullcontext) -> Window:
    """Drive the cell's traffic for ``seconds`` through the served path.
    ``span(name)`` wraps the window, each serve call and each wait (the
    traced run passes a ``HostSpans``)."""
    mix, pool = served.cell.mix, served.pool
    calls, returned = [], []
    lowered = served.lowered
    lowered.n, lowered.on = 0, True
    clock = time.perf_counter
    if mix["kind"] == "closed":
        orders = traffic.closed_calls(mix, seed)
        with span("bench_window"):
            t0 = clock()
            while True:
                idx = next(orders)
                with span("bench_serve"):
                    out = served.serve([pool[i] for i in idx])
                t = clock() - t0
                calls.append((idx, out))
                returned.append(np.full(len(idx), t))
                if t >= seconds:
                    break
        arrival = np.zeros(sum(len(i) for i, _ in calls))
        returned = np.concatenate(returned)
        elapsed, due = float(returned.max()), len(returned)
    else:
        t_arr, idx_arr, _ = traffic.camera_arrivals(mix, seed, seconds)
        cap, n, i = mix["max_frames_per_call"], len(t_arr), 0
        returned = np.full(n, np.nan)
        with span("bench_window"):
            t0 = clock()
            while i < n:
                now = clock() - t0
                if t_arr[i] > now:
                    with span("bench_wait"):
                        time.sleep(t_arr[i] - now)
                    continue
                j = min(int(np.searchsorted(t_arr, now, "right")), i + cap)
                idx = idx_arr[i:j]
                with span("bench_serve"):
                    out = served.serve([pool[k] for k in idx])
                t = clock() - t0
                calls.append((idx, out))
                returned[i:j] = t
                i = j
                if t > seconds + DRAIN_S:
                    break
        arrival, due = t_arr, n
        elapsed = float(np.nanmax(returned)) if np.isfinite(returned).any() else 0.0
    lowered.on = False
    return Window(calls, arrival, returned, elapsed, due, lowered.n, t0)


def reference_logits(served: Served, window: Window) -> np.ndarray:
    """The reference's logits of every pool frame the window served
    (other rows stay NaN), in blocks of ``REF_BLOCK`` frames."""
    c = served.cell.config
    fwd = jax.jit(functools.partial(served.reference.forward, cfg=c,
                                    num=ops.NUMERICS[c["reference"]]))
    used = np.unique(np.concatenate([idx for idx, _ in window.calls]))
    out = np.full((len(served.pool), c["num_classes"]), np.nan, np.float32)
    for s in range(0, len(used), REF_BLOCK):
        block = used[s:s + REF_BLOCK]
        rows = np.resize(block, REF_BLOCK)  # fixed shape: one compile
        out[block] = np.asarray(fwd(served.params, served.pool[rows]))[:len(block)]
    return out


def verify(served: Served, window: Window) -> dict:
    ref = reference_logits(served, window)
    return check.compare(window.calls, ref, served.cell.config["limits"],
                         window.due)


def end_to_end(name: str, window: Window, setup_s: float) -> float:
    if name == "setup_s":
        return setup_s
    if name == "frames_per_s":
        return window.due / window.seconds
    lat = 1e3 * (window.returned - window.arrival)
    lat = np.where(np.isfinite(lat), lat, np.inf)
    if name == "latency_p50_ms":
        return float(np.percentile(lat, 50))
    if name == "latency_p99_ms":
        return float(np.percentile(lat, 99))
    raise BenchError(f"no end-to-end metric {name!r}")


@dataclasses.dataclass
class TraceContext:
    trace: object
    frames: int
    flops_per_frame: int
    peak: dict
    log: object


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class HostSpans:
    """The client's spans on the profiler's clock.  The profiler's
    timeline starts where its session starts (``start_trace``); a span is
    kept as (name, start ns, end ns, {}) from ``origin``, the host clock
    read just before the session starts.  On a v5e the two origins lie
    some 20 us apart.  The profiler's own host tracer is off: it records
    every tile of the frames' host-to-device transpose and slows the
    served loop some tenfold."""

    def __init__(self):
        self.spans = []
        self.origin = time.time_ns()

    @contextlib.contextmanager
    def __call__(self, name: str):
        start = time.time_ns()
        try:
            yield
        finally:
            self.spans.append((name, float(start - self.origin),
                               float(time.time_ns() - self.origin), {}))


def traced_window(served: Served, seed: int, seconds: float, logdir: str):
    """A window under the profiler, device tracer only; returns the
    window and the client's spans."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 0
    spans = HostSpans()
    jax.profiler.start_trace(logdir, profiler_options=opts)
    try:
        window = measure(served, seed, seconds, span=spans)
    finally:
        jax.profiler.stop_trace()
    return window, spans.spans


def peak_bytes(devices) -> int:
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
