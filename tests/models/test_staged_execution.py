"""Staged (multi-chip) execution vs the monolithic graph executor, and
the first-class node-keyed impl overrides."""
from fractions import Fraction as F

import jax
import numpy as np
import pytest

from repro.core import LayerSpec, plan_graph
from repro.core.graph import LayerGraph
from repro.models import cnn
from repro.models.registry import get_cnn_api


def _pw(name, d_in, d_out, hw=(8, 8)):
    return LayerSpec(name=name, kind="pointwise", d_in=d_in, d_out=d_out,
                     in_hw=hw, out_hw=hw, activation="relu")


def _small_graph():
    """stem -> two-layer trunk + shortcut -> add -> head (6 nodes)."""
    g = LayerGraph()
    prev = g.add(_pw("stem", 4, 8))
    stem = prev
    for i in range(2):
        prev = g.add(_pw(f"trunk{i}", 8, 8), [prev])
    prev = g.add(LayerSpec(name="join", kind="add", d_in=8, d_out=8,
                           in_hw=(8, 8), out_hw=(8, 8)), [prev, stem])
    g.add(_pw("head", 8, 4), [prev])
    return g


@pytest.fixture(scope="module")
def small():
    g = _small_graph()
    params = cnn.init_graph_params(g, jax.random.key(0))
    x = jax.random.normal(jax.random.key(1), (2, 8, 8, 4))
    return g, params, x


# ---------------------------------------------------------------------------
# apply_staged == apply_graph
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("family", ["resnet18", "mobilenet_v2",
                                    "efficientnet_b0"])
@pytest.mark.parametrize("n_stages", [2, 3])
def test_staged_equals_monolithic_fp32(family, n_stages):
    """Acceptance: staged fp32 output allclose to the monolithic pass for
    ResNet-18, MobileNet-v2 and EfficientNet-B0 at S in {2, 3} — with
    each stage jitted separately and the internal cut-tensor cross-check
    active."""
    api = get_cnn_api(family)
    cfg = api.make_config(input_hw=(32, 32), num_classes=10)
    params = api.init(cfg, jax.random.key(0))
    x = jax.random.normal(jax.random.key(1), (1, 32, 32, 3))
    mono = api.apply(params, x, cfg)
    gp = api.partition(cfg, F(3), n_stages)
    assert gp.stage_plan.n_stages == n_stages
    staged = api.apply_staged(params, x, cfg, partition=gp,
                              check_monolithic=True)
    assert np.allclose(np.asarray(staged), np.asarray(mono),
                       rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("family", ["resnet18", "mobilenet_v2"])
def test_staged_int8_bit_exact(family):
    """Acceptance: the int8 datapath through the staged executor (eager,
    so the op sequence is identical) is bit-exact vs the monolithic."""
    api = get_cnn_api(family)
    cfg = api.make_config(input_hw=(32, 32), num_classes=10)
    params = api.init(cfg, jax.random.key(0))
    x = jax.random.normal(jax.random.key(1), (1, 32, 32, 3))
    q, s = api.quantize(params)
    mono = api.apply_int8(q, s, x, cfg)
    gp = api.partition(cfg, F(3), 3)
    staged = api.apply_int8(q, s, x, cfg, partition=gp, jit=False)
    assert np.array_equal(np.asarray(staged), np.asarray(mono))


def test_staged_with_rate_matched_plan(small):
    """The staged executor composes with the rate-matched kernel path:
    per-node Pallas tiles dispatched inside each stage's trace, with the
    executed-tile == plan assertion still active."""
    g, params, x = small
    gp = plan_graph(g, F(2), n_stages=2)
    kp = gp.kernel_plan()
    mono = cnn.apply_graph(params, x, g, plan=kp)
    executed = {}
    staged = cnn.apply_staged(params, x, g, partition=gp, plan=kp,
                              executed=executed)
    assert np.allclose(np.asarray(staged), np.asarray(mono),
                       rtol=1e-5, atol=1e-5)
    planned = [n for n, p in kp.items() if p.has_kernel]
    assert sorted(executed) == sorted(planned)


def test_staged_forward_amortizes_tracing(small):
    """staged_forward compiles each stage once: repeated calls hit the
    jit cache (trace-time work runs once), unlike one-shot apply_staged
    which rebuilds the pipeline per call."""
    g, params, x = small
    gp = plan_graph(g, F(2), n_stages=2)
    traces = []

    def counting_pw(a, w):
        traces.append(1)
        return jax.numpy.einsum("bhwc,cd->bhwd", a, w)

    fwd = cnn.staged_forward(g, partition=gp,
                             overrides={"trunk0": counting_pw})
    y1 = fwd(params, x)["head"]
    y2 = fwd(params, x)["head"]
    assert len(traces) == 1                      # traced once, reused
    assert np.array_equal(np.asarray(y1), np.asarray(y2))
    mono = cnn.apply_graph(params, x, g, overrides={"trunk0": counting_pw})
    assert np.allclose(np.asarray(y1), np.asarray(mono), rtol=1e-5, atol=1e-5)


def test_staged_accepts_stage_plan_directly(small):
    g, params, x = small
    gp = plan_graph(g, F(2), n_stages=3)
    a = cnn.apply_staged(params, x, g, partition=gp)
    b = cnn.apply_staged(params, x, g, partition=gp.stage_plan)
    assert np.array_equal(np.asarray(a), np.asarray(b))


def test_staged_rejects_bad_partitions(small):
    g, params, x = small
    with pytest.raises(cnn.GraphExecutionError):   # unstaged GraphPlan
        cnn.apply_staged(params, x, g, partition=plan_graph(g, F(2)))
    other = plan_graph(_small_graph(), F(2), n_stages=2).stage_plan
    wrong = plan_graph(
        LayerGraph.from_chain([_pw("a", 4, 8), _pw("b", 8, 4)]),
        F(2), n_stages=2,
    ).stage_plan
    with pytest.raises(cnn.GraphExecutionError):   # different graph
        cnn.apply_staged(params, x, g, partition=wrong)
    # a structurally identical partition built from an equal graph is fine
    assert cnn.apply_staged(params, x, g, partition=other) is not None


# ---------------------------------------------------------------------------
# first-class node-keyed overrides
# ---------------------------------------------------------------------------

def test_override_wins_and_is_exempt_from_tile_assertion(small):
    """A user impl for one node rides along with a kernel plan: the node
    runs the override (no tile record) and the executed==plan assertion
    does not fire for it, while every other node is still checked."""
    g, params, x = small
    kp = plan_graph(g, F(2)).kernel_plan()
    calls = []

    def my_pointwise(a, w):
        calls.append("hit")
        return jax.numpy.einsum("bhwc,cd->bhwd", a, w)

    executed = {}
    y = cnn.apply_graph(params, x, g, plan=kp,
                        overrides={"trunk0": my_pointwise},
                        executed=executed)
    assert calls                                   # the override ran
    assert "trunk0" not in executed                # and claimed no tile
    ref = cnn.apply_graph(params, x, g, plan=kp)
    assert np.allclose(np.asarray(y), np.asarray(ref), rtol=1e-5, atol=1e-5)


def test_override_without_plan(small):
    g, params, x = small
    y_ref = cnn.apply_graph(params, x, g)
    doubled = cnn.apply_graph(
        params, x, g,
        overrides={"head": lambda a, w: 2.0 * (a @ w)},
    )
    assert not np.allclose(np.asarray(doubled), np.asarray(y_ref))


def test_override_validation(small):
    g, params, x = small
    with pytest.raises(cnn.GraphExecutionError):   # unknown node
        cnn.apply_graph(params, x, g, overrides={"nope": lambda a, w: a})
    with pytest.raises(cnn.GraphExecutionError):   # wiring node
        cnn.apply_graph(params, x, g, overrides={"join": lambda a, w: a})


def test_override_that_records_is_still_validated(small):
    """If a user override *does* record into the shared executed dict,
    its claim is held to the plan like any kernel's."""
    g, params, x = small
    kp = plan_graph(g, F(2)).kernel_plan()

    def lying_impl(a, w):
        return jax.numpy.einsum("bhwc,cd->bhwd", a, w)

    executed = {"trunk0": {"bk": 1, "bn": 1, "d_in": 8, "d_out": 8}}
    with pytest.raises(cnn.GraphExecutionError):
        cnn.apply_graph(params, x, g, plan=kp,
                        overrides={"trunk0": lying_impl},
                        executed=executed)


def test_override_threads_through_model_wrappers():
    api = get_cnn_api("resnet18")
    cfg = api.make_config(input_hw=(32, 32), num_classes=10)
    params = api.init(cfg, jax.random.key(0))
    x = jax.random.normal(jax.random.key(1), (1, 32, 32, 3))
    seen = []

    def spy_dense(a, w):
        seen.append(a.shape)
        return a @ w

    y = api.apply(params, x, cfg, overrides={"fc": spy_dense})
    assert seen and y.shape == (1, 10)
