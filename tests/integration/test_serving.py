"""Serving engine integration: continuous batching, slot reuse, ordering."""
import jax
import numpy as np
import pytest

from repro.configs.registry import get_config, reduced
from repro.models.lm_api import get_api
from repro.serving.engine import Engine, Request


@pytest.fixture(scope="module")
def setup():
    cfg = reduced(get_config("qwen2-7b"), layers=2, d_model=64, vocab=128)
    api = get_api(cfg)
    params = api.init(cfg, jax.random.key(0))
    return cfg, params


def test_engine_drains_burst(setup):
    cfg, params = setup
    eng = Engine(cfg, params, slots=2, max_len=64)
    rng = np.random.default_rng(0)
    reqs = []
    for i in range(5):     # 5 requests > 2 slots: forces slot reuse
        r = Request(rid=i, prompt=rng.integers(0, 128, size=4 + i).astype(
            np.int32), max_new=6)
        reqs.append(r)
        eng.submit(r)
    eng.run_until_drained()
    assert all(r.done for r in reqs)
    assert all(len(r.out) >= 6 for r in reqs)


def test_engine_greedy_matches_manual_decode(setup):
    """Tokens from the batched engine == single-request greedy decode."""
    cfg, params = setup
    api = get_api(cfg)
    prompt = np.asarray([3, 14, 15, 9, 2], np.int32)

    # manual single-request reference
    state = api.make_serve_state(cfg, 1, 64)
    logits, state = api.prefill(params, {"tokens": jax.numpy.asarray(
        prompt)[None]}, state, cfg)
    want = [int(jax.numpy.argmax(logits[0, -1]))]
    pos = len(prompt)
    for _ in range(5):
        logits, state = api.decode(
            params, state,
            {"tokens": jax.numpy.asarray([[want[-1]]], jax.numpy.int32)},
            jax.numpy.asarray(pos, jax.numpy.int32), cfg)
        want.append(int(jax.numpy.argmax(logits[0, -1])))
        pos += 1

    eng = Engine(cfg, params, slots=3, max_len=64)
    # distractor requests occupy other slots
    rng = np.random.default_rng(1)
    eng.submit(Request(rid=100, prompt=rng.integers(0, 128, size=7).astype(
        np.int32), max_new=6))
    target = Request(rid=0, prompt=prompt, max_new=6)
    eng.submit(target)
    eng.run_until_drained()
    assert target.out == want, (target.out, want)


def test_engine_rejects_encdec(setup):
    from repro.configs.registry import get_config, reduced
    cfg = reduced(get_config("seamless-m4t-medium"))
    with pytest.raises(ValueError):
        Engine(cfg, {}, slots=1)
