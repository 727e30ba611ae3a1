"""Plain float32 references of the served CNN families.

One module per family (``bench/reference/<family>.py``), found by the
``family`` of a configuration file.  Each module gives:

* ``layers(cfg)``: the weighted layers in order, as ``ops.Layer``s;
* ``forward(params, x, cfg, num)``: logits [N, classes] at the
  ``ops.Numerics`` ``num`` (float32 at the highest precision by default);
* the weights come from ``ops.init(layers(cfg), key)``, in the parameter
  layout the served path takes (``{node: {"w": ..., "b": ...}}``).

Nothing here imports the program: the architectures are written out
from their papers, and the weights are made from the seed.
"""

from __future__ import annotations

import importlib


def load(family: str):
    """The reference module of ``family``."""
    return importlib.import_module(f"bench.reference.{family}")
