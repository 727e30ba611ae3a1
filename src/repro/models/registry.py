"""The CNN registry: one lookup and one apply machinery for every family.

  api = get_cnn_api("resnet18")   # or mobilenet_v1/v2, resnet34,
                                  # efficientnet_b0, regnety_4gf
  cfg = api.make_config(input_hw=(32, 32), num_classes=10)
  params = api.init(cfg, rng)
  logits = api.apply(params, x, cfg)     # conv_impls= swaps in Pallas
  q, s = api.quantize(params); api.apply_int8(q, s, x, cfg)
  api.graph(cfg) -> the LayerGraph the DSE plans (same description).
  kp = api.plan(cfg, input_rate)         # per-node ImplPlan table
  logits = api.apply(params, x, cfg, plan=kp)   # rate-matched tiling

A family module contributes its config dataclass (``cfg.graph()``,
``cfg.dtype``) and nothing else; every entry point here is the shared
executor (``models/cnn.py``) bound once to those two.  The
language-model families have their own API (``models.lm_api``), which
this module does not import.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Tuple

from repro.models import cnn, efficientnet, mobilenet, regnet, resnet


@dataclasses.dataclass(frozen=True)
class CNNApi:
    """Uniform surface over the CNN families.

    All apply machinery is shared (models/cnn.py interprets the family's
    LayerGraph); a family contributes only its config type and its graph
    builder, so adding one is a one-line registration below.

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

    ``serve(params, frames, cfg, input_rate=..., n_stages=S,
    config=ServeConfig(...))`` is the streaming front door
    (``serving.cnn_stream.serve_frames``): plan at ``input_rate``,
    partition into ``n_stages``, micro-batch admitted frames, and pump
    them through the per-stage pipeline with BestRate admission control
    and bounded inter-stage queues.  Returns ``(outputs, ServeReport)``.
    Everything about the run is the ``serving.ServeConfig``: the
    rate-matched path is ``config.kernel_plan``
    (``partition(...).kernel_plan(batch=config.microbatch)``, built once
    by the caller), and ``config.execute="devices"`` places each stage
    on its own device.  A config without a dtype gets ``cfg.dtype``.

    Every ``CNNApi`` owns a set of memo ``caches`` (graphs per config,
    DSE plans per (config, rate, stages), compiled ``StagePipeline``s
    per identity key): repeated ``apply_staged``/``serve`` calls hit
    the per-stage jit cache instead of rebuilding and retracing every
    stage per call.
    """

    family: str
    make_config: Callable            # (**overrides) -> cfg dataclass
    init: Callable                   # (cfg, rng) -> params
    apply: Callable                  # (params, x, cfg, *, conv_impls, plan, overrides, check)
    quantize: Callable               # (params, bits=8) -> (q_params, scales)
    apply_int8: Callable             # (q_params, scales, x, cfg, *, plan, overrides, partition, jit)
    graph: Callable                  # (cfg) -> LayerGraph (the DSE's view)
    plan: Callable                   # (cfg, input_rate, **kw) -> ImplPlan table
    partition: Callable              # (cfg, input_rate, n_stages, **kw) -> GraphPlan
    apply_staged: Callable           # (params, x, cfg, *, partition, ...)
    serve: Callable                  # (params, frames, cfg, **kw) -> (out, report)
    caches: Any = None               # {"graphs", "plans", "pipelines"} memo dicts


def _cnn_api(family: str, make_config: Callable) -> CNNApi:
    """Build one family's ``CNNApi`` with its private memo caches.

    Every entry point is the shared executor bound to the family's
    graph (``graph(cfg)``) and ``cfg.dtype``.  ``graphs`` memoizes
    ``cfg.graph()`` per (hashable, frozen) config so repeated calls see
    the *same* ``LayerGraph`` object — the identity the pipeline cache
    keys on.  ``plans`` memoizes the DSE per (config, rate, stages,
    kwargs) when the kwargs are hashable.  ``pipelines`` is handed to
    ``models.cnn.stage_functions(cache=...)`` (and, via
    ``ServeConfig.pipeline_cache``, to the serving engine), so the
    compiled per-stage jit functions are reused across calls.
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

    def init(cfg, rng):
        """He-init weights + folded-BN bias for every arithmetic node."""
        return cnn.init_graph_params(graph(cfg), rng, cfg.dtype)

    def apply(params, x, cfg, *, conv_impls=None, plan=None, overrides=None,
              check=True):
        """Forward pass.  ``x``: [N, H, W, 3].  Returns logits [N, classes].

        ``conv_impls`` may override {'conv', 'dwconv', 'pointwise',
        'dense', 'scale'} with kernel-backed implementations (see
        ``cnn.kernel_impls``); ``plan`` (a ``GraphPlan.kernel_plan()``
        table) runs the rate-matched path instead — each node's Pallas
        call tiled per its own DSE choice; ``overrides`` supplies
        node-name-keyed impls that win over both.
        """
        return cnn.apply_graph(params, x, graph(cfg), impls=conv_impls,
                               plan=plan, overrides=overrides,
                               dtype=cfg.dtype, check=check)

    def apply_staged(params, x, cfg, *, conv_impls=None, **kwargs):
        """``cnn.apply_staged`` on the family's graph, memoizing the
        compiled stage pipeline in ``pipelines`` unless ``cache=`` is
        given."""
        kwargs.setdefault("cache", pipelines)
        return cnn.apply_staged(params, x, graph(cfg), impls=conv_impls,
                                dtype=cfg.dtype, **kwargs)

    def apply_int8(q_params, scales, x, cfg, *, plan=None, overrides=None,
                   partition=None, jit=True):
        """Inference with int8 weights dequantized on the fly (see
        ``cnn.apply_int8``)."""
        return cnn.apply_int8(q_params, scales, x, graph(cfg), plan=plan,
                              overrides=overrides, partition=partition,
                              dtype=cfg.dtype, jit=jit)

    def serve(params, frames, cfg, *, config=None, **kwargs):
        from repro.serving.cnn_stream import serve_frames
        from repro.serving.config import ServeConfig

        if config is None:
            config = ServeConfig()
        if config.dtype is None:
            config = config.with_(dtype=cfg.dtype)
        if config.pipeline_cache is None:
            config = config.with_(pipeline_cache=pipelines)
        kwargs.setdefault("plan_cache", plans)
        return serve_frames(graph(cfg), params, frames, config=config,
                            **kwargs)

    return CNNApi(
        family=family,
        make_config=make_config,
        init=init,
        apply=apply,
        quantize=cnn.quantize_params,
        apply_int8=apply_int8,
        graph=graph,
        plan=plan,
        partition=partition,
        apply_staged=apply_staged,
        serve=serve,
        caches={"graphs": graphs, "plans": plans, "pipelines": pipelines},
    )


# family name -> its config constructor; everything else is shared
_CNN_FAMILIES: Dict[str, Callable] = {
    "efficientnet_b0": efficientnet.EfficientNetConfig,
    "mobilenet_v1": functools.partial(mobilenet.MobileNetConfig, version=1),
    "mobilenet_v2": functools.partial(mobilenet.MobileNetConfig, version=2),
    "regnety_4gf": regnet.RegNetConfig,
    "resnet18": functools.partial(resnet.ResNetConfig, depth=18),
    "resnet34": functools.partial(resnet.ResNetConfig, depth=34),
}


def cnn_families() -> Tuple[str, ...]:
    return tuple(sorted(_CNN_FAMILIES))


def get_cnn_api(name: str) -> CNNApi:
    try:
        make_config = _CNN_FAMILIES[name]
    except KeyError:
        raise KeyError(
            f"unknown CNN family {name!r}; known: {', '.join(cnn_families())}"
        ) from None
    return _cnn_api(name, make_config)
