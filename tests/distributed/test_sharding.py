"""Sharding rules: divisibility guards, rule coverage, constraint helper."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.distributed import sharding as shd

# `with mesh:` and with_sharding_constraint need Auto axes (jax.make_mesh
# defaults to Explicit ones).
_AUTO = (jax.sharding.AxisType.Auto,) * 2


@pytest.fixture(scope="module")
def mesh():
    # single-device mesh with the production axis names: rules must emit
    # specs whose axis sizes (1) divide everything -> specs still correct.
    return jax.make_mesh((1, 1), ("data", "model"), axis_types=_AUTO)


def test_param_rules(mesh):
    cases = {
        "embed": ((1024, 64), P("model", None)),
        "blocks_dense/attn/wq": ((4, 64, 64), P(None, ("data",), "model")),
        "blocks_dense/attn/wo": ((4, 64, 64), P(None, "model", ("data",))),
        "blocks_dense/ffn/w_up": ((4, 64, 128), P(None, ("data",), "model")),
        "blocks_dense/ffn/w_down": ((4, 128, 64), P(None, "model", ("data",))),
        "blocks_moe/moe/w_up": ((4, 8, 64, 128),
                                P(None, None, ("data",), "model")),
        "blocks_dense/ln1": ((64,), P()),
        "step": ((), P()),
    }
    for path, (shape, want) in cases.items():
        got = shd.param_spec(path, shape, mesh)
        assert got == want, f"{path}: {got} != {want}"


def test_divisibility_guard():
    import numpy as np
    import types
    mesh1 = jax.make_mesh((1, 1), ("data", "model"), axis_types=_AUTO)
    assert shd._fits(92553, mesh1, "model")     # 1 divides everything
    # production-shaped stub (mesh_sizes only reads names + shape)
    fm = types.SimpleNamespace(axis_names=("data", "model"),
                               devices=np.zeros((16, 16)))
    assert not shd._fits(92553, fm, "model")    # internvl2 vocab is odd
    assert shd._fits(92544, fm, "model")
    assert not shd._fits(8, fm, "model")        # grok: 8 experts < 16-way
    assert shd._fits(512, fm, ("data", "model"))   # 512 % 256 == 0


def test_batch_and_serve_specs(mesh):
    batch = {"tokens": jax.ShapeDtypeStruct((8, 16), jnp.int32)}
    sh = shd.batch_specs(batch, mesh)
    assert sh["tokens"].spec == P(("data",))
    kv = jax.ShapeDtypeStruct((4, 8, 512, 2, 16), jnp.bfloat16)  # [L,B,S,kv,dh]
    sh = shd.serve_state_specs({"k": kv}, mesh)
    assert sh["k"].spec == P(None, ("data",), "model", None, None)
    # batch-1 long context: shard the sequence instead
    kv1 = jax.ShapeDtypeStruct((4, 1, 2048, 2, 16), jnp.bfloat16)
    sh = shd.serve_state_specs({"k": kv1}, mesh)
    assert sh["k"].spec == P(None, None, (("data", "model")), None, None)


def test_constrain_noop_outside_mesh():
    x = jnp.ones((4, 8, 16))
    y = shd.constrain(x, ("batch", "seq", None))
    assert (y == x).all()


def test_constrain_inside_mesh(mesh):
    def f(x):
        return shd.constrain(x, ("batch", "seq", None)) * 2
    x = jnp.ones((4, 8, 16))
    with mesh:
        out = jax.jit(f)(x)
    assert (out == 2).all()


def test_opt_state_shardings_follow_params(mesh):
    from repro.optim.optimizers import AdamWConfig, init as adam_init
    params = {"blocks_dense": {"ffn": {"w_up": jnp.zeros((4, 64, 128))}}}
    st = adam_init(AdamWConfig(), params)
    sh = shd.opt_state_shardings(st, params, mesh)
    assert sh.mu["blocks_dense"]["ffn"]["w_up"].spec == \
        P(None, ("data",), "model")
    assert sh.step.spec == P()
