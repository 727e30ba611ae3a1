"""Training launcher: builds the jit'd train_step and runs the loop.

``build_train_step`` is shared by the dry-run (lower/compile only) and
the real loop below (examples/train_lm.py drives it on CPU).  The loop
wires in every fault-tolerance feature: async checkpointing + auto-
resume, straggler watchdog, heartbeat, resumable data stream.
"""
from __future__ import annotations

import time
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from repro.checkpoint.checkpointer import Checkpointer
from repro.configs.base import ModelConfig
from repro.data.pipeline import SyntheticLM
from repro.distributed.fault_tolerance import Heartbeat, StragglerWatchdog
from repro.models.lm_api import get_api
from repro.optim import optimizers as opt


def build_train_step(cfg: ModelConfig, adam: opt.AdamWConfig,
                     grad_shardings=None) -> Callable:
    """(params, opt_state, batch) -> (params, opt_state, metrics).

    Includes gradient accumulation (cfg.grad_accum microbatches) — the
    activation-memory valve that keeps the train_4k cells inside
    16 GB/chip.  ``grad_shardings`` pins gradients to the FSDP layout
    (see optimizers.accumulate_grads).
    """
    api = get_api(cfg)

    def loss(params, batch):
        return api.loss_fn(params, batch, cfg)

    def step(params, opt_state, batch):
        l, metrics, grads = opt.accumulate_grads(
            loss, params, batch, cfg.grad_accum,
            grad_shardings=grad_shardings,
            acc_dtype=jnp.dtype(cfg.grad_accum_dtype))
        params, opt_state, om = opt.apply_updates(adam, params, grads,
                                                  opt_state)
        metrics = dict(metrics)
        metrics.update(om, loss=l)
        return params, opt_state, metrics

    return step


def adam_config_for(cfg: ModelConfig, **overrides) -> opt.AdamWConfig:
    base = dict(mu_dtype=cfg.adam_mu_dtype, nu_dtype=cfg.adam_nu_dtype,
                factored=cfg.adam_factored, momentum=cfg.adam_momentum)
    base.update(overrides)
    return opt.AdamWConfig(**base)


# ---------------------------------------------------------------------------
# the actual loop (CPU-runnable; multi-host launch wires the same pieces)
# ---------------------------------------------------------------------------

def train_loop(
    cfg: ModelConfig,
    *,
    steps: int,
    batch: int,
    seq_len: int,
    lr: float = 3e-4,
    ckpt_dir: Optional[str] = None,
    ckpt_every: int = 50,
    log_every: int = 10,
    seed: int = 0,
    on_metrics: Optional[Callable[[int, Dict], None]] = None,
) -> Dict[str, Any]:
    api = get_api(cfg)
    adam = adam_config_for(cfg, lr=lr, total_steps=steps,
                           warmup_steps=max(1, steps // 20))
    params = api.init(cfg, jax.random.key(seed))
    opt_state = opt.init(adam, params)
    data = SyntheticLM(vocab=cfg.vocab, seq_len=seq_len, batch=batch,
                       seed=seed)
    step_fn = jax.jit(build_train_step(cfg, adam), donate_argnums=(0, 1))

    ck = Checkpointer(ckpt_dir) if ckpt_dir else None
    start = 0
    if ck and ck.latest_step() is not None:
        (params, opt_state), extra = ck.restore(None, (params, opt_state))
        data.load_state_dict(extra["data"])
        start = extra["step"]
        print(f"[train] resumed from step {start}")

    wd = StragglerWatchdog()
    hb = Heartbeat(f"{ckpt_dir}/heartbeat.json") if ckpt_dir else None
    losses = []
    for step in range(start, steps):
        t0 = time.perf_counter()
        b = {k: jnp.asarray(v) for k, v in next(data).items()}
        params, opt_state, metrics = step_fn(params, opt_state, b)
        loss = float(metrics["loss"])
        dt = time.perf_counter() - t0
        losses.append(loss)
        if hb:
            hb.beat(step)
        if wd.observe(step, dt):
            print(f"[train] WARN straggling at step {step} "
                  f"({dt:.2f}s); flagged={wd.flagged_steps[-3:]}")
            wd.reset()
        if step % log_every == 0:
            print(f"[train] step {step:5d} loss {loss:.4f} "
                  f"gnorm {float(metrics['grad_norm']):.3f} {dt * 1e3:.0f}ms")
        if on_metrics:
            on_metrics(step, metrics)
        if ck and (step + 1) % ckpt_every == 0:
            ck.save(step + 1, (params, opt_state),
                    extra={"step": step + 1, "data": data.state_dict()})
    if ck:
        ck.wait()
    return {"params": params, "losses": losses}
