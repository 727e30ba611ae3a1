"""Uniform API across the five language-model families.

Everything the launcher / dry-run needs:
  api = get_api(cfg)
  api.init(cfg, rng) -> params
  api.loss_fn(params, batch, cfg) -> (loss, metrics)
  api.make_serve_state(cfg, batch, max_len) -> cache/state pytree
  api.prefill(params, batch, state, cfg) -> (logits, state)
  api.decode(params, state, batch, pos, cfg) -> (logits, state)
  train_batch_specs / prefill_batch_specs / decode_batch_specs /
  serve_state_specs(cfg, shape) -> jax.ShapeDtypeStruct pytrees (no
      allocation — dry-run safe).

The CNN families have their own registry (``models.registry``), which
imports none of this.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.configs.shapes import ShapeSuite
from repro.models import encdec, hybrid, lm, mamba, vlm


@dataclasses.dataclass(frozen=True)
class ModelAPI:
    init: Callable
    loss_fn: Callable
    make_serve_state: Callable        # (cfg, batch, max_len) -> pytree
    prefill: Callable                 # (params, batch, state, cfg)
    decode: Callable                  # (params, state, batch, pos, cfg)


# --------------------------------------------------------------------------
# family adapters (normalize calling conventions)
# --------------------------------------------------------------------------

def _lm_api() -> ModelAPI:
    return ModelAPI(
        init=lm.init,
        loss_fn=lm.loss_fn,
        make_serve_state=lambda cfg, b, ml: lm.init_cache(cfg, b, ml),
        prefill=lambda p, batch, st, cfg: lm.prefill(p, batch["tokens"], cfg, st),
        decode=lambda p, st, batch, pos, cfg: lm.decode_step(
            p, st, batch["tokens"], pos, cfg),
    )


def _ssm_api() -> ModelAPI:
    return ModelAPI(
        init=mamba.init,
        loss_fn=mamba.loss_fn,
        make_serve_state=lambda cfg, b, ml: mamba.init_state(cfg, b),
        prefill=lambda p, batch, st, cfg: mamba.prefill(
            p, batch["tokens"], cfg, st),
        decode=lambda p, st, batch, pos, cfg: mamba.decode_step(
            p, st, batch["tokens"], pos, cfg),
    )


def _hybrid_api() -> ModelAPI:
    return ModelAPI(
        init=hybrid.init,
        loss_fn=hybrid.loss_fn,
        make_serve_state=lambda cfg, b, ml: hybrid.init_state(cfg, b, ml),
        prefill=lambda p, batch, st, cfg: hybrid.prefill(
            p, batch["tokens"], cfg, st),
        decode=lambda p, st, batch, pos, cfg: hybrid.decode_step(
            p, st, batch["tokens"], pos, cfg),
    )


def _encdec_api() -> ModelAPI:
    def _make_state(cfg, b, ml):
        # serve state carries the decoder KV cache AND the encoder memory
        # (cross-attention source) so decode steps are self-contained.
        return {"cache": encdec.init_cache(cfg, b, ml),
                "memory": jnp.zeros((b, ml, cfg.d_model), cfg.dtype)}

    def _prefill(p, batch, st, cfg):
        logits, cache, memory = encdec.prefill(
            p, batch["tokens"], batch["frames"], cfg, st["cache"])
        return logits, {"cache": cache, "memory": memory}

    def _decode(p, st, batch, pos, cfg):
        logits, cache = encdec.decode_step(
            p, st["cache"], st["memory"], batch["tokens"], pos, cfg)
        return logits, {"cache": cache, "memory": st["memory"]}

    return ModelAPI(
        init=encdec.init,
        loss_fn=encdec.loss_fn,
        make_serve_state=_make_state,
        prefill=_prefill,
        decode=_decode,
    )


def _vlm_api() -> ModelAPI:
    return ModelAPI(
        init=vlm.init,
        loss_fn=vlm.loss_fn,
        make_serve_state=lambda cfg, b, ml: vlm.init_cache(cfg, b, ml),
        prefill=lambda p, batch, st, cfg: vlm.prefill(
            p, batch["tokens"], batch["patches"], cfg, st),
        decode=lambda p, st, batch, pos, cfg: vlm.decode_step(
            p, st, batch["tokens"], pos, cfg),
    )


_FAMILIES = {
    "lm": _lm_api, "ssm": _ssm_api, "hybrid": _hybrid_api,
    "encdec": _encdec_api, "vlm": _vlm_api,
}


def get_api(cfg: ModelConfig) -> ModelAPI:
    return _FAMILIES[cfg.family]()


# --------------------------------------------------------------------------
# abstract input specs (ShapeDtypeStruct — dry-run safe, no allocation)
# --------------------------------------------------------------------------

def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def train_batch_specs(cfg: ModelConfig, shape: ShapeSuite) -> Dict[str, Any]:
    b, s = shape.global_batch, shape.seq_len
    if cfg.family == "encdec":
        return {
            "frames": _sds((b, s, cfg.d_model), jnp.bfloat16),
            "tokens": _sds((b, s), jnp.int32),
            "labels": _sds((b, s), jnp.int32),
        }
    if cfg.family == "vlm":
        st = s - cfg.n_patches
        return {
            "patches": _sds((b, cfg.n_patches, cfg.d_model), jnp.bfloat16),
            "tokens": _sds((b, st), jnp.int32),
            "labels": _sds((b, st), jnp.int32),
        }
    return {
        "tokens": _sds((b, s), jnp.int32),
        "labels": _sds((b, s), jnp.int32),
    }


def prefill_batch_specs(cfg: ModelConfig, shape: ShapeSuite) -> Dict[str, Any]:
    b, s = shape.global_batch, shape.seq_len
    if cfg.family == "encdec":
        return {
            "frames": _sds((b, s, cfg.d_model), jnp.bfloat16),
            "tokens": _sds((b, s), jnp.int32),
        }
    if cfg.family == "vlm":
        return {
            "patches": _sds((b, cfg.n_patches, cfg.d_model), jnp.bfloat16),
            "tokens": _sds((b, s - cfg.n_patches), jnp.int32),
        }
    return {"tokens": _sds((b, s), jnp.int32)}


def decode_batch_specs(cfg: ModelConfig, shape: ShapeSuite) -> Dict[str, Any]:
    return {"tokens": _sds((shape.global_batch, 1), jnp.int32)}


def serve_state_specs(cfg: ModelConfig, shape: ShapeSuite) -> Any:
    """Abstract version of make_serve_state (shapes only)."""
    api = get_api(cfg)
    return jax.eval_shape(
        lambda: api.make_serve_state(cfg, shape.global_batch, shape.seq_len))
