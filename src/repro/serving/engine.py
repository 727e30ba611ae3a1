"""Batched serving engine: continuous batching over a slotted KV cache.

The rate calculus shows up twice (DESIGN.md §3):
  * prefill produces KV at ~seq_len tokens/step while decode consumes at
    1 token/step/slot — the paper's pooling-layer rate drop, so the
    engine schedules prefills and decodes separately (disaggregation) and
    sizes the decode batch to keep the arithmetic busy
    (``core.stage_partition.allocate_chips`` does the chip split in the
    multi-chip deployment; here the single-host engine keeps the slot
    pool full, which is the same constraint);
  * slot admission = Eq. (9): a new request is admitted only when a slot
    (capacity) is free — continuous flow without overfetch.

Implementation notes: fixed-size slot pool, greedy sampling, per-slot
position counters, one jit'd decode for the whole pool (padded slots are
masked by their own cache_len).  Works with every decoder-capable arch in
``models.lm_api``.  CNN families stream through the frame-level engine in
``serving.cnn_stream`` instead (same admission calculus, frames for
tokens).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models.lm_api import get_api


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray  # [S] int32
    max_new: int = 16
    out: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    t_submit: float = 0.0
    t_first: Optional[float] = None
    t_done: Optional[float] = None


class Engine:
    def __init__(
        self,
        cfg: ModelConfig,
        params,
        *,
        slots: int = 4,
        max_len: int = 256,
        eos: Optional[int] = None,
    ):
        family = getattr(cfg, "family", None)
        if family not in ("lm", "ssm", "hybrid"):
            # CNN configs (MobileNetConfig / ResNetConfig) carry no
            # .family at all — they are LayerGraph builders, not
            # ModelConfigs — so detect them structurally too.
            is_cnn = (family or "").startswith(("mobilenet", "resnet")) or (
                family is None and hasattr(cfg, "graph")
            )
            if is_cnn:
                raise ValueError(
                    f"Engine serves token streams; CNN config "
                    f"{type(cfg).__name__} streams frames through "
                    "serving.cnn_stream.CNNStreamEngine (front door: "
                    "registry.CNNApi.serve)"
                )
            raise ValueError(
                f"Engine supports text-in/text-out families; {family} "
                "(encdec/vlm) needs the modality-aware driver in examples/"
            )
        self.cfg = cfg
        self.params = params
        self.api = get_api(cfg)
        self.slots = slots
        self.max_len = max_len
        self.eos = eos
        self.active: Dict[int, Request] = {}  # slot -> request
        self.queue: List[Request] = []
        self.pos = np.zeros(slots, np.int32)
        self.state = self.api.make_serve_state(cfg, slots, max_len)

        def _decode_fn(p, st, toks, pos):
            return self.api.decode(p, st, {"tokens": toks}, pos, cfg)

        def _prefill_fn(p, toks, st1):
            return self.api.prefill(p, {"tokens": toks}, st1, cfg)

        self._decode = jax.jit(_decode_fn)
        self._prefill_one = jax.jit(_prefill_fn)

    # ------------------------------------------------------------------
    def submit(self, req: Request) -> None:
        req.t_submit = time.perf_counter()
        self.queue.append(req)

    def _free_slots(self) -> List[int]:
        return [i for i in range(self.slots) if i not in self.active]

    def _admit(self) -> None:
        """Admission = capacity check (Eq. 9 analogue)."""
        for slot in self._free_slots():
            if not self.queue:
                break
            req = self.queue.pop(0)
            toks = jnp.asarray(req.prompt, jnp.int32)[None]
            state1 = self.api.make_serve_state(self.cfg, 1, self.max_len)
            logits, state1 = self._prefill_one(self.params, toks, state1)
            tok = int(jnp.argmax(logits[0, -1]))
            req.out.append(tok)
            req.t_first = time.perf_counter()
            # copy the single-request state into the pool slot; per-layer
            # list caches (mixed-window models) carry batch at dim 0,
            # stacked caches at dim 1.
            bdim = 0 if isinstance(self.state, list) else 1

            def _write_slot(pool, one):
                if pool.ndim < 2:
                    return pool
                return jax.lax.dynamic_update_slice_in_dim(
                    pool, one.astype(pool.dtype), slot, axis=bdim
                )

            self.state = jax.tree.map(_write_slot, self.state, state1)
            self.pos[slot] = len(req.prompt)
            self.active[slot] = req

    # ------------------------------------------------------------------
    def step(self) -> int:
        """One engine tick: admit, batched decode, retire.  Returns the
        number of tokens produced."""
        self._admit()
        if not self.active:
            return 0
        toks = np.zeros((self.slots, 1), np.int32)
        for slot, req in self.active.items():
            toks[slot, 0] = req.out[-1] if req.out else 0
        # per-slot positions: attention vmaps the cache write per row and
        # masks per-row kv_len, so heterogeneous slots decode in one batch.
        pos = jnp.asarray(self.pos, jnp.int32)
        logits, self.state = self._decode(
            self.params, self.state, jnp.asarray(toks), pos
        )
        nxt = np.asarray(jnp.argmax(logits[:, -1], axis=-1), np.int32)
        made = 0
        for slot, req in list(self.active.items()):
            tok = int(nxt[slot])
            req.out.append(tok)
            made += 1
            self.pos[slot] += 1
            if (
                (self.eos is not None and tok == self.eos)
                or len(req.out) >= req.max_new
                or self.pos[slot] >= self.max_len - 1
            ):
                req.done = True
                req.t_done = time.perf_counter()
                del self.active[slot]
        return made

    def run_until_drained(self, max_ticks: int = 10_000) -> None:
        for _ in range(max_ticks):
            if not self.queue and not self.active:
                return
            self.step()
        raise RuntimeError("engine did not drain")
