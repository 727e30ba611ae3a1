"""The unified CNN registry: one lookup + one apply machinery, 6 families."""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro.core.flops import graph_macs
from repro.models import cnn
from repro.models.registry import cnn_families, get_cnn_api

FAMILIES = ("efficientnet_b0", "mobilenet_v1", "mobilenet_v2", "regnety_4gf",
            "resnet18", "resnet34")


def test_registry_lists_all_families():
    assert cnn_families() == tuple(sorted(FAMILIES))


def test_unknown_family_raises_with_candidates():
    with pytest.raises(KeyError, match="resnet18"):
        get_cnn_api("vgg16")


@pytest.mark.parametrize("family", FAMILIES)
def test_family_end_to_end(family):
    """Every registered family: config -> init -> apply -> finite logits,
    with the executor's per-node shape/MAC asserts active throughout."""
    api = get_cnn_api(family)
    cfg = api.make_config(input_hw=(32, 32), num_classes=10)
    params = api.init(cfg, jax.random.key(0))
    x = jax.random.normal(jax.random.key(1), (2, 32, 32, 3))
    logits = api.apply(params, x, cfg)
    assert logits.shape == (2, 10)
    assert bool(jnp.all(jnp.isfinite(logits)))
    graph = api.graph(cfg)
    assert graph_macs(graph) > 0
    arith = {n for n in graph.topo_order()
             if graph.spec(n).kind in cnn.ARITH_KINDS}
    assert arith == set(params)


@pytest.mark.parametrize("family", FAMILIES)
def test_family_int8_roundtrip(family):
    api = get_cnn_api(family)
    cfg = api.make_config(input_hw=(32, 32), num_classes=10)
    params = api.init(cfg, jax.random.key(0))
    x = jax.random.normal(jax.random.key(1), (2, 32, 32, 3))
    q, scales = api.quantize(params)
    logits = api.apply_int8(q, scales, x, cfg)
    assert logits.shape == (2, 10)
    assert bool(jnp.all(jnp.isfinite(logits)))


def test_activation_tags_follow_the_papers_datapaths():
    """MobileNet runs relu6 (linear bottleneck on projections); ResNet
    runs relu with the post-add placement.  The executable nonlinearity
    comes from the spec, so check it on the specs."""
    mn = get_cnn_api("mobilenet_v2")
    g = mn.graph(mn.make_config())
    assert g.spec("b3_project").activation == "none"
    assert g.spec("b3_expand").activation == "relu6"
    rg = get_cnn_api("resnet18").graph(get_cnn_api("resnet18").make_config())
    assert rg.spec("l1b1_conv2").activation == "none"
    assert rg.spec("l1b1_add").activation == "relu"
    assert rg.spec("fc").activation == "none"


def test_cnn_front_door_imports_no_language_model_code():
    """A fresh interpreter that imports the CNN registry and the serving
    package loads none of the language-model stack."""
    code = textwrap.dedent("""
        import sys
        import repro.models.registry, repro.serving
        lm = [m for m in sys.modules
              if m in ("repro.models.lm", "repro.models.encdec",
                       "repro.models.hybrid", "repro.models.mamba",
                       "repro.models.vlm", "repro.models.lm_api",
                       "repro.nn.attention", "repro.nn.moe", "repro.nn.ssm",
                       "repro.serving.engine")
              or m == "repro.configs" or m.startswith("repro.configs.")]
        print(sorted(lm))
    """)
    src = Path(__file__).resolve().parents[2] / "src"
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(src))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "[]"
