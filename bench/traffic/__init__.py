"""The one traffic generator.  A mix is a data file,
``bench/traffic/<mix>.json``, whose ``kind`` picks one of two shapes:

* ``closed`` — one client, ``frames_per_call`` frames per call, the next
  call sent when the previous one returns (offline analytics over
  recorded footage);
* ``cameras`` — an open loop of ``cameras`` live cameras, ``fps`` frames
  per second each, every camera with a seeded phase and every frame with
  a seeded jitter of up to ``jitter_ms``; the client sends whatever has
  arrived, up to ``max_frames_per_call`` frames a call.

Frames are drawn from a seeded pool of ``pool_frames`` frames.  A seed
changes which pool frame goes where and the phases and jitters, never
the amount of work: every seed sends the same number of frames from
each camera, and the phases are spread one to each 1/cameras of a
period.
"""

from __future__ import annotations

import json
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
KINDS = ("closed", "cameras")


def load(name: str) -> dict:
    """The mix ``bench/traffic/<name>.json``."""
    with open(os.path.join(HERE, f"{name}.json")) as f:
        mix = json.load(f)
    if mix.get("kind") not in KINDS:
        raise ValueError(f"traffic {name!r}: kind {mix.get('kind')!r} not in {KINDS}")
    return mix


def rng(seed: int, stream: int) -> np.random.Generator:
    """An independent generator per (seed, stream); any whole seed."""
    return np.random.default_rng([stream, seed % 2**64])


def pool(seed: int, frames: int, hw, channels: int = 3) -> np.ndarray:
    """The seeded frame pool, [frames, H, W, C] float32 on the host
    (normalised pixels, standard normal)."""
    return rng(seed, 0).standard_normal((frames, *hw, channels), np.float32)


def closed_calls(mix: dict, seed: int):
    """Endless pool indices for a closed loop: one array of
    ``frames_per_call`` per call, walking seeded permutations of the pool."""
    g = rng(seed, 1)
    n, per = mix["pool_frames"], mix["frames_per_call"]
    order = np.empty(0, np.int64)
    while True:
        while len(order) < per:
            order = np.concatenate([order, g.permutation(n)])
        yield order[:per]
        order = order[per:]


def camera_arrivals(mix: dict, seed: int, seconds: float):
    """Arrivals of an open loop of cameras over about ``seconds``: each
    camera sends ``round(seconds * fps)`` frames from its phase on.
    Returns sorted times in seconds, the pool index and the camera of
    each frame."""
    g = rng(seed, 2)
    cams, fps = mix["cameras"], float(mix["fps"])
    period = 1.0 / fps
    slot = g.permutation(cams)
    phase = (slot + g.random(cams)) * period / cams
    k = np.arange(max(1, round(seconds * fps)))
    t = phase[:, None] + k[None, :] * period
    t = t + g.uniform(-1.0, 1.0, t.shape) * mix["jitter_ms"] * 1e-3
    t = np.maximum(t, 0.0)
    cam = np.broadcast_to(np.arange(cams)[:, None], t.shape)
    idx = g.integers(0, mix["pool_frames"], t.shape)
    order = np.argsort(t, axis=None, kind="stable")
    return t.ravel()[order], idx.ravel()[order], cam.ravel()[order]
