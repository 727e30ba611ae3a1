"""The traffic generator: seeded, repeatable, and holding the camera rate
and jitter its mixes state."""

import numpy as np
import pytest

from bench import traffic

CAMS = {"kind": "cameras", "cameras": 12, "fps": 30, "jitter_ms": 2,
        "max_frames_per_call": 64, "pool_frames": 256}
CLOSED = {"kind": "closed", "frames_per_call": 64, "pool_frames": 256}
BIG = 2**31 + 12345


def _calls(seed, n=10):
    gen = traffic.closed_calls(CLOSED, seed)
    return np.stack([next(gen) for _ in range(n)])


@pytest.mark.parametrize("seed", [0, 7, BIG, 2**40 + 1])
def test_same_seed_same_schedule(seed):
    np.testing.assert_array_equal(_calls(seed), _calls(seed))
    a = traffic.camera_arrivals(CAMS, seed, 2.0)
    b = traffic.camera_arrivals(CAMS, seed, 2.0)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    np.testing.assert_array_equal(traffic.pool(seed, 4, (8, 8)),
                                  traffic.pool(seed, 4, (8, 8)))


def test_different_seed_different_schedule():
    assert not np.array_equal(_calls(1), _calls(2))
    assert not np.array_equal(traffic.camera_arrivals(CAMS, 1, 2.0)[0],
                              traffic.camera_arrivals(CAMS, 2, 2.0)[0])
    assert not np.array_equal(traffic.pool(1, 4, (8, 8)),
                              traffic.pool(2, 4, (8, 8)))


def test_closed_walks_whole_permutations():
    calls = _calls(3, n=8)  # 8 x 64 = two passes over the 256-frame pool
    assert calls.shape == (8, 64)
    for half in (calls[:4], calls[4:]):
        assert sorted(half.ravel()) == list(range(256))


@pytest.mark.parametrize("seed", [0, BIG])
def test_camera_rate_and_jitter_hold(seed):
    seconds = 3.0
    t, idx, cam = traffic.camera_arrivals(CAMS, seed, seconds)
    assert np.all(np.diff(t) >= 0)
    assert len(t) == CAMS["cameras"] * round(seconds * CAMS["fps"])
    assert idx.min() >= 0 and idx.max() < CAMS["pool_frames"]
    period, jitter = 1 / CAMS["fps"], CAMS["jitter_ms"] * 1e-3
    for c in range(CAMS["cameras"]):
        tc = t[cam == c]
        assert len(tc) == round(seconds * CAMS["fps"])
        # every frame lies within the jitter of its slot on the camera's
        # period; the slots are one period apart
        k = np.arange(len(tc))
        phase = np.median(tc - k * period)
        assert np.all(np.abs(tc - k * period - phase) <= 2 * jitter + 1e-12)
        assert 0 <= phase < period + jitter


def test_camera_phases_spread_over_the_period():
    seconds, period = 3.0, 1 / CAMS["fps"]
    t, _, cam = traffic.camera_arrivals(CAMS, 5, seconds)
    k = np.arange(round(seconds * CAMS["fps"]))
    phase = np.sort([np.median(t[cam == c] - k * period)
                     for c in range(CAMS["cameras"])])
    # one phase in each 1/cameras of the period: no two strata empty in a
    # row (the median leaves well under a millisecond of jitter)
    gaps = np.diff(np.concatenate([phase, [phase[0] + period]]))
    assert gaps.max() < 2 * period / CAMS["cameras"] + 1e-3


@pytest.mark.parametrize("name", ["saturate", "stream"])
def test_committed_mixes_load(name):
    mix = traffic.load(name)
    assert mix["kind"] in traffic.KINDS
    assert mix["pool_frames"] > 0


def test_unknown_kind_is_refused(tmp_path, monkeypatch):
    (tmp_path / "odd.json").write_text('{"kind": "poisson"}')
    monkeypatch.setattr(traffic, "HERE", str(tmp_path))
    with pytest.raises(ValueError, match="poisson"):
        traffic.load("odd")
