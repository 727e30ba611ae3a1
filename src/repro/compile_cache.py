"""JAX's persistent compilation cache for the repo's entry points.

``use_compile_cache(root)`` is called once, before anything compiles, by
``chip_smoke.py`` and ``benchmarks/run.py``.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing is
changed.  Otherwise the cache is the fixed ``<root>/.jax_cache/``: never a
temporary or per-process path, so a later run from the same checkout finds
what an earlier one compiled.

``CacheEvents`` counts the cache's hits and misses (writes) from JAX's
monitoring events, so a run can say whether it compiled or loaded.
"""

from __future__ import annotations

import os

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"


def use_compile_cache(root: str) -> str:
    """Turn the persistent cache on; returns the directory in use."""
    path = os.environ.get(ENV)
    if not path:
        path = os.path.join(os.path.abspath(root), ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


class CacheEvents:
    """Running count of persistent-cache hits and misses in this process."""

    _EVENTS = {
        "/jax/compilation_cache/cache_hits": "hits",
        "/jax/compilation_cache/cache_misses": "misses",
    }

    def __init__(self) -> None:
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_) -> None:
        name = self._EVENTS.get(event)
        if name is not None:
            setattr(self, name, getattr(self, name) + 1)

    def snapshot(self) -> tuple:
        return self.hits, self.misses
