"""The served kernels compile for a TPU v5e at the published 224x224 widths.

Each case takes one node of a family's kernel plan (``kernel_plan(batch=8)``,
the micro-batch the smoke run serves), builds the executor's impl for it
(``models.cnn.kernel_impls``) and compiles it with XLA:TPU for a described
``v5e:2x2`` topology — no chip needed.  The compile refuses what interpret
mode accepts: strided value slices, blocks that are not (8, 128)-legal, and
working sets above the VMEM limit.  A passing case has the Mosaic kernel in
its compiled program (``tpu_custom_call``).

The topology is described inside a module fixture, never at import: only one
process at a time may load the TPU library.
"""

from fractions import Fraction as F

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.graph import LayerGraph, plan_graph
from repro.models import cnn
from repro.models.registry import get_cnn_api
from repro.models.topology import conv_spec

BATCH = 8
RATE = F(3)


@pytest.fixture(scope="module")
def topo():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        from jax.experimental import topologies

        try:
            yield topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    # compiles for an unattached chip are written to the persistent cache
    # but can never be read back without one
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _family_graph(family):
    api = get_cnn_api(family)
    return api.graph(api.make_config())


def _wide_conv_graph():
    # 3x3 stride-1 conv over a 112x112x32 frame: 18 MiB of VMEM, over the
    # 16 MiB default scoped limit
    g = LayerGraph()
    spec, _ = conv_spec("c3x3_112", "conv", 32, 32, (112, 112), 3, 1)
    g.add(spec)
    return g


GRAPHS = {
    "resnet18": lambda: _family_graph("resnet18"),
    "mobilenet_v2": lambda: _family_graph("mobilenet_v2"),
    "efficientnet_b0": lambda: _family_graph("efficientnet_b0"),
    "regnety_4gf": lambda: _family_graph("regnety_4gf"),
    "wide": _wide_conv_graph,
}

CASES = [
    ("resnet18", "conv1", jnp.float32),  # 7x7/2 stem, 3 channels: im2col
    ("resnet18", "l1b1_conv1", jnp.float32),  # 3x3/1 at 56x56x64
    ("resnet18", "l2b1_conv1", jnp.float32),  # 3x3/2, 56 -> 28
    ("resnet18", "l2b1_down", jnp.float32),  # 1x1/2 projection
    ("resnet18", "l3b1_conv1", jnp.float32),  # 3x3/2, 28 -> 14
    ("resnet18", "l3b2_conv2", jnp.float32),  # 3x3/1 at 14x14x256
    ("resnet18", "l4b1_conv1", jnp.float32),  # 3x3/2, 14 -> 7
    ("resnet18", "l4b2_conv1", jnp.float32),  # 3x3/1 at 7x7x512
    ("resnet18", "l4b2_conv2", jnp.float32),
    ("resnet18", "fc", jnp.float32),  # 512 -> 1000 head
    ("mobilenet_v2", "conv1", jnp.float32),  # 3x3/2 stem: im2col
    ("mobilenet_v2", "b1_dw", jnp.float32),  # dw 3x3/1 at 112x112x32
    ("mobilenet_v2", "b2_expand", jnp.float32),  # pointwise at 112x112
    ("mobilenet_v2", "b2_dw", jnp.float32),  # dw 3x3/2, 112 -> 56, C=96
    ("mobilenet_v2", "b2_dw", jnp.bfloat16),
    ("mobilenet_v2", "b1_dw", jnp.bfloat16),
    ("efficientnet_b0", "b4_dw", jnp.float32),  # dw 5x5/2, 56 -> 28, C=144
    ("efficientnet_b0", "b10_dw", jnp.float32),  # dw 5x5/1 at 14x14x672
    ("efficientnet_b0", "b2_se_reduce", jnp.float32),  # SE dense 96 -> 4
    ("efficientnet_b0", "b2_se_expand", jnp.float32),  # SE dense 4 -> 96
    ("efficientnet_b0", "b1_scale", jnp.float32),  # se_scale at 112x112x32
    ("efficientnet_b0", "b6_scale", jnp.float32),  # se_scale at 28x28x240
    ("efficientnet_b0", "b16_scale", jnp.float32),  # se_scale at 7x7x1152
    ("regnety_4gf", "s1b1_b", jnp.float32),  # grouped 3x3/2, 112 -> 56, G=2
    ("regnety_4gf", "s1b2_b", jnp.float32),  # grouped 3x3/1 at 56x56x128
    ("regnety_4gf", "s2b2_b", jnp.float32),  # G=3, 28x28x192
    ("regnety_4gf", "s3b1_b", jnp.float32),  # G=8, 3x3/2, 28 -> 14
    ("regnety_4gf", "s3b2_b", jnp.float32),  # G=8, 14x14x512
    ("regnety_4gf", "s4b1_b", jnp.float32),  # G=17, 3x3/2, 14 -> 7
    ("regnety_4gf", "s4b2_b", jnp.float32),  # G=17, 7x7x1088
    ("regnety_4gf", "s1b1_proj", jnp.float32),  # 1x1/2 projection, 112 -> 56
    ("regnety_4gf", "s1b1_scale", jnp.float32),  # se_scale at 56x56x128
    ("wide", "c3x3_112", jnp.float32),
]

# groups per grid step of RegNetY's grouped convs: whole lane tiles of
# 64-channel groups, or every group where none divides (G = 3, 17)
GPB = {"s1b1_b": 2, "s1b2_b": 2, "s2b2_b": 3, "s3b1_b": 2, "s3b2_b": 2,
       "s4b1_b": 17, "s4b2_b": 17}


# frames per grid step of the whole-frame convs: the planned bm (448-512
# pixels at layers 1-2, 392 at layers 3-4) over one frame's output pixels
FRAMES = {
    "l1b1_conv1": 1,
    "l2b1_conv1": 1,
    "l3b1_conv1": 2,
    "l3b2_conv2": 2,
    "l4b1_conv1": 8,
    "l4b2_conv1": 8,
    "l4b2_conv2": 8,
}

# columns each tap window streams through the MXU: the output width
# rounded up to the 8 float32 sublanes (56 at layer 1 is aligned already)
WO_MXU = {
    "l1b1_conv1": 56,
    "l2b1_conv1": 32,
    "l2b1_down": 32,
    "l3b1_conv1": 16,
    "l3b2_conv2": 16,
    "l4b1_conv1": 8,
    "l4b2_conv1": 8,
    "l4b2_conv2": 8,
}


def _operands(spec, dtype, sharding):
    n = BATCH
    if spec.kind == "dense":
        x = (n, spec.d_in)
    else:
        x = (n, *spec.in_hw, spec.d_in)
    # a scale join's second operand is its gate, one [C] a frame
    w = (n, spec.d_in) if spec.kind == "scale" else cnn._weight_shape(spec)
    return [
        jax.ShapeDtypeStruct(s, dtype, sharding=sharding) for s in (x, w)
    ]


@pytest.mark.parametrize(
    "family,node,dtype",
    CASES,
    ids=[f"{f}-{n}-{jnp.dtype(d).name}" for f, n, d in CASES],
)
def test_served_kernel_compiles_for_v5e(
    one_chip, no_compile_cache, family, node, dtype
):
    graph = GRAPHS[family]()
    gp = plan_graph(graph, RATE)
    node_plan = gp.kernel_plan(
        batch=BATCH, dtype_bytes=jnp.dtype(dtype).itemsize
    )[node]
    spec = graph.spec(node)
    executed = {}
    impl = cnn.kernel_impls(plan={node: node_plan}, executed=executed)[node]
    if spec.kind in ("conv", "dwconv"):
        def fn(x, w):
            return impl(x, w, spec.stride[0])
    else:
        fn = impl
    compiled = jax.jit(fn).lower(*_operands(spec, dtype, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert executed[node]["bk"] == node_plan.tile.bk
    assert executed[node]["bn"] == node_plan.tile.bn
    if family == "resnet18" and node in FRAMES:
        ho, wo = spec.out_hw
        assert executed[node]["frames"] == FRAMES[node]
        assert FRAMES[node] == max(1, node_plan.tile.bm // (ho * wo))
    if family == "resnet18" and node in WO_MXU:
        assert executed[node]["wo_mxu"] == WO_MXU[node]
    if family == "regnety_4gf" and node in GPB:
        assert (executed[node]["groups"], executed[node]["gpb"]) == (
            spec.groups, GPB[node])
        assert not node_plan.tile.im2col
