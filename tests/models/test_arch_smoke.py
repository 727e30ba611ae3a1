"""Per-architecture smoke tests (task spec requirement): a REDUCED config
of each family runs one forward/train step + a prefill/decode round on
CPU, asserting shapes and finiteness."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import ARCHS, get_config, reduced
from repro.configs.shapes import ShapeSuite
from repro.models.lm_api import get_api, train_batch_specs

SMALL = ShapeSuite("smoke", seq_len=32, global_batch=2, kind="train")


def _batch(cfg, rng):
    specs = train_batch_specs(cfg, SMALL)
    out = {}
    for k, s in specs.items():
        rng, sub = jax.random.split(rng)
        if s.dtype == jnp.int32:
            out[k] = jax.random.randint(sub, s.shape, 0, cfg.vocab, jnp.int32)
        else:
            out[k] = jax.random.normal(sub, s.shape, jnp.float32).astype(s.dtype)
    return out


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_train_step_smoke(arch):
    cfg = reduced(get_config(arch))
    api = get_api(cfg)
    rng = jax.random.key(0)
    params = api.init(cfg, rng)
    batch = _batch(cfg, jax.random.key(1))

    loss, metrics = api.loss_fn(params, batch, cfg)
    assert loss.shape == ()
    assert bool(jnp.isfinite(loss)), f"{arch}: non-finite loss"

    # one gradient step moves the loss (end-to-end differentiability)
    grads = jax.grad(lambda p: api.loss_fn(p, batch, cfg)[0])(params)
    gnorm = jax.tree.reduce(
        lambda a, g: a + jnp.sum(jnp.square(g.astype(jnp.float32))),
        grads, jnp.zeros(()))
    assert bool(jnp.isfinite(gnorm)) and float(gnorm) > 0
    params2 = jax.tree.map(lambda p, g: p - 0.05 * g.astype(p.dtype),
                           params, grads)
    loss2, _ = api.loss_fn(params2, batch, cfg)
    assert bool(jnp.isfinite(loss2))
    assert float(loss2) < float(loss) + 1e-3, f"{arch}: step didn't help"


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_prefill_decode_smoke(arch):
    cfg = reduced(get_config(arch))
    api = get_api(cfg)
    params = api.init(cfg, jax.random.key(0))
    b, s_pref, max_len = 2, 8, 16

    batch = {"tokens": jax.random.randint(jax.random.key(1), (b, s_pref),
                                          0, cfg.vocab, jnp.int32)}
    if cfg.family == "encdec":
        batch["frames"] = jax.random.normal(jax.random.key(2),
                                            (b, s_pref, cfg.d_model))
    if cfg.family == "vlm":
        batch["patches"] = jax.random.normal(jax.random.key(2),
                                             (b, cfg.n_patches, cfg.d_model))

    state = api.make_serve_state(cfg, b, max_len)
    logits, state = api.prefill(params, batch, state, cfg)
    assert logits.shape == (b, 1, cfg.vocab)
    assert bool(jnp.all(jnp.isfinite(logits)))

    pos = s_pref + (cfg.n_patches if cfg.family == "vlm" else 0)
    tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
    for step in range(3):
        logits, state = api.decode(params, state, {"tokens": tok},
                                   jnp.asarray(pos + step, jnp.int32), cfg)
        assert logits.shape == (b, 1, cfg.vocab)
        assert bool(jnp.all(jnp.isfinite(logits)))
        tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]


@pytest.mark.parametrize("arch", ["gemma3-1b", "qwen2-7b", "internvl2-2b"])
def test_prefill_decode_consistency(arch):
    """Decode continuation must match teacher-forced forward logits —
    the KV cache path agrees with the full-sequence path."""
    cfg = reduced(get_config(arch))
    api = get_api(cfg)
    params = api.init(cfg, jax.random.key(0))
    b, s = 2, 12
    toks = jax.random.randint(jax.random.key(1), (b, s), 0, cfg.vocab,
                              jnp.int32)

    from repro.models import lm, vlm
    if cfg.family == "vlm":
        patches = jax.random.normal(jax.random.key(2),
                                    (b, cfg.n_patches, cfg.d_model))
        full, _ = vlm.forward(params, toks, patches, cfg)
    else:
        full, _ = lm.forward(params, toks, cfg)

    prefix = 8
    batch = {"tokens": toks[:, :prefix]}
    if cfg.family == "vlm":
        batch["patches"] = patches
    state = api.make_serve_state(
        cfg, b, s + (cfg.n_patches if cfg.family == "vlm" else 0))
    logits, state = api.prefill(params, batch, state, cfg)
    off = cfg.n_patches if cfg.family == "vlm" else 0
    np.testing.assert_allclose(
        np.asarray(logits[:, 0]), np.asarray(full[:, off + prefix - 1]),
        rtol=2e-3, atol=2e-3)

    for i in range(prefix, s):
        logits, state = api.decode(params, state,
                                   {"tokens": toks[:, i:i + 1]},
                                   jnp.asarray(off + i, jnp.int32), cfg)
        np.testing.assert_allclose(
            np.asarray(logits[:, 0]), np.asarray(full[:, off + i]),
            rtol=2e-3, atol=2e-3,
            err_msg=f"{arch}: decode step {i} diverges from forward")


def test_param_count_formulas():
    """param_count must match the actual initialized tree (reduced cfgs)."""
    from repro.configs.base import param_count
    for arch in ("qwen2-7b", "gemma3-1b", "grok-1-314b", "mamba2-780m",
                 "seamless-m4t-medium"):
        cfg = reduced(get_config(arch))
        api = get_api(cfg)
        params = api.init(cfg, jax.random.key(0))
        actual = sum(int(np.prod(x.shape))
                     for x in jax.tree.leaves(params))
        predicted = param_count(cfg)
        assert abs(actual - predicted) / actual < 0.06, (
            f"{arch}: predicted {predicted:,} vs actual {actual:,}")
