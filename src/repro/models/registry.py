"""Uniform model API across the five LM families + the CNN registry.

LM side — everything the launcher / dry-run needs:
  api = get_api(cfg)
  api.init(cfg, rng) -> params
  api.loss_fn(params, batch, cfg) -> (loss, metrics)
  api.make_serve_state(cfg, batch, max_len) -> cache/state pytree
  api.prefill(params, batch, state, cfg) -> (logits, state)
  api.decode(params, state, batch, pos, cfg) -> (logits, state)
  train_batch_specs(cfg, shape) / serve_specs(cfg, shape) ->
      jax.ShapeDtypeStruct pytrees (no allocation — dry-run safe).

CNN side — the paper's workloads, same lookup shape:
  api = get_cnn_api("resnet18")          # or mobilenet_v1/v2, resnet34
  cfg = api.make_config(input_hw=(32, 32), num_classes=10)
  params = api.init(cfg, rng)
  logits = api.apply(params, x, cfg)     # conv_impls= swaps in Pallas
  q, s = api.quantize(params); api.apply_int8(q, s, x, cfg)
  api.graph(cfg) -> the LayerGraph the DSE plans (same description).
  kp = api.plan(cfg, input_rate)         # per-node ImplPlan table
  logits = api.apply(params, x, cfg, plan=kp)   # rate-matched tiling
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.configs.shapes import ShapeSuite
from repro.models import encdec, hybrid, lm, mamba, mobilenet, resnet, vlm


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
# CNN registry (the paper's workloads: shared apply machinery, models/cnn.py)
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class CNNApi:
    """Uniform surface over the CNN families (mirrors ModelAPI's shape).

    All apply machinery is shared (models/cnn.py interprets the family's
    LayerGraph); a family contributes only its config type and its graph
    builder, so adding one is a ~10-line registration below.

    ``plan(cfg, input_rate, **dse_kwargs)`` runs the DAG DSE on the
    family's graph and lowers it to the per-node ``ImplPlan`` table
    (``core.graph.GraphPlan.kernel_plan``); pass the result to
    ``apply(..., plan=kp)`` / ``apply_int8(..., plan=kp)`` for
    rate-matched per-layer Pallas tiling (vs the uniform
    ``conv_impls=cnn.kernel_impls()`` path).

    ``partition(cfg, input_rate, n_stages, **dse_kwargs)`` is the
    multi-chip front door: the stage-aware DSE cuts the family's DAG
    into ``n_stages`` chips (min-bottleneck over DSE-selected mults,
    cut-crossing skew FIFOs sized as inter-chip stream buffers) and
    returns the ``GraphPlan`` with ``stage_plan`` / ``stream_bufs``
    populated.  Feed it to ``apply_staged(params, x, cfg,
    partition=gp)`` to run each stage as its own jitted subgraph.

    ``serve(params, frames, cfg, input_rate=..., n_stages=S, ...)`` is
    the streaming front door (``serving.cnn_stream``): plan at
    ``input_rate``, partition into ``n_stages``, micro-batch admitted
    frames to the batch-pinned kernel tiles, and pump them through the
    per-stage pipeline with BestRate admission control and bounded
    inter-stage queues.  Returns ``(outputs, ServeReport)``.
    ``serve(..., execute="devices")`` places each stage on its own
    device (stage ``s`` on ``jax.devices()[s]``) so the engine pumps
    genuinely overlapped stages — wall-clock, not only ticks.

    Every ``CNNApi`` owns a set of memo ``caches`` (graphs per config,
    DSE plans per (config, rate, stages), compiled ``StagePipeline``s
    per identity key): repeated ``apply_staged``/``serve`` calls hit
    the per-stage jit cache instead of rebuilding and retracing every
    stage per call.
    """

    family: str
    make_config: Callable            # (**overrides) -> cfg dataclass
    init: Callable                   # (cfg, rng) -> params
    apply: Callable                  # (params, x, cfg, *, conv_impls, plan)
    quantize: Callable               # (params, bits=8) -> (q_params, scales)
    apply_int8: Callable             # (q_params, scales, x, cfg) -> logits
    graph: Callable                  # (cfg) -> LayerGraph (the DSE's view)
    plan: Callable                   # (cfg, input_rate, **kw) -> ImplPlan table
    partition: Callable              # (cfg, input_rate, n_stages, **kw) -> GraphPlan
    apply_staged: Callable           # (params, x, cfg, *, partition, ...)
    serve: Callable                  # (params, frames, cfg, **kw) -> (out, report)
    caches: Any = None               # {"graphs", "plans", "pipelines"} memo dicts


def _cnn_api(family: str, make_config: Callable, mod) -> CNNApi:
    """Build one family's ``CNNApi`` with its private memo caches.

    ``graphs`` memoizes ``cfg.graph()`` per (hashable, frozen) config so
    repeated calls see the *same* ``LayerGraph`` object — the identity
    the pipeline cache keys on.  ``plans`` memoizes the DSE per
    (config, rate, stages, kwargs) when the kwargs are hashable.
    ``pipelines`` is handed to ``models.cnn.stage_functions(cache=...)``
    (and, via ``ServeConfig.pipeline_cache``, to the serving engine), so
    the compiled per-stage jit functions are reused across calls.
    """
    graphs: Dict[Any, Any] = {}
    plans: Dict[Any, Any] = {}
    pipelines: Dict[Any, Any] = {}

    def graph(cfg):
        try:
            hit = graphs.get(cfg)
        except TypeError:  # unhashable config: build fresh, skip the memo
            return cfg.graph()
        if hit is None:
            hit = cfg.graph()
            graphs[cfg] = hit
        return hit

    def _planned(cfg, input_rate, n_stages, dse_kwargs):
        from fractions import Fraction

        from repro.core.graph import plan_graph

        g = graph(cfg)
        try:
            key = (cfg, Fraction(input_rate), n_stages,
                   tuple(sorted(dse_kwargs.items())))
            hit = plans.get(key)
        except TypeError:  # unhashable rate/kwargs: plan fresh
            key, hit = None, None
        if hit is None:
            if n_stages is None:
                hit = plan_graph(g, input_rate, **dse_kwargs)
            else:
                hit = plan_graph(g, input_rate, n_stages=n_stages, **dse_kwargs)
            if key is not None:
                plans[key] = hit
        return hit

    def plan(cfg, input_rate, **dse_kwargs):
        return _planned(cfg, input_rate, None, dse_kwargs).kernel_plan()

    def partition(cfg, input_rate, n_stages, **dse_kwargs):
        return _planned(cfg, input_rate, n_stages, dse_kwargs)

    def apply_staged(params, x, cfg, **kwargs):
        kwargs.setdefault("cache", pipelines)
        kwargs.setdefault("graph", graph(cfg))
        return mod.apply_staged(params, x, cfg, **kwargs)

    def serve(params, frames, cfg, **kwargs):
        from repro.serving.cnn_stream import serve_frames
        from repro.serving.config import ServeConfig

        config = kwargs.pop("config", None)
        if "dtype" not in kwargs and (config is None or config.dtype is None):
            kwargs["dtype"] = cfg.dtype
        if config is None:
            config = ServeConfig()
        if config.pipeline_cache is None:
            config = config.with_(pipeline_cache=pipelines)
        kwargs["config"] = config
        kwargs.setdefault("plan_cache", plans)
        return serve_frames(graph(cfg), params, frames, **kwargs)

    return CNNApi(
        family=family,
        make_config=make_config,
        init=mod.init_params,
        apply=mod.apply,
        quantize=mod.quantize_params,
        apply_int8=mod.apply_int8,
        graph=graph,
        plan=plan,
        partition=partition,
        apply_staged=apply_staged,
        serve=serve,
        caches={"graphs": graphs, "plans": plans, "pipelines": pipelines},
    )


def _mobilenet_api(version: int) -> CNNApi:
    return _cnn_api(
        f"mobilenet_v{version}",
        functools.partial(mobilenet.MobileNetConfig, version=version),
        mobilenet,
    )


def _resnet_api(depth: int) -> CNNApi:
    return _cnn_api(
        f"resnet{depth}",
        functools.partial(resnet.ResNetConfig, depth=depth),
        resnet,
    )


_CNN_FAMILIES: Dict[str, Callable[[], CNNApi]] = {
    "mobilenet_v1": functools.partial(_mobilenet_api, 1),
    "mobilenet_v2": functools.partial(_mobilenet_api, 2),
    "resnet18": functools.partial(_resnet_api, 18),
    "resnet34": functools.partial(_resnet_api, 34),
}


def cnn_families() -> Tuple[str, ...]:
    return tuple(sorted(_CNN_FAMILIES))


def get_cnn_api(name: str) -> CNNApi:
    try:
        return _CNN_FAMILIES[name]()
    except KeyError:
        raise KeyError(
            f"unknown CNN family {name!r}; known: {', '.join(cnn_families())}"
        ) from None


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
