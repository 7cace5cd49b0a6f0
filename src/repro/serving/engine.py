"""Continuous-batching serving engine over a DynIMS-managed KV pool.

The paper's architecture in the serving path: HBM is the contended
resource; the *compute tenant* is the model's weights + activation
working set, the *storage tenant* is the KV cache.  The
:class:`~repro.core.store.KVBlockPool` bookkeeps block grants; a
:class:`~repro.core.plane.MemoryPlane` (device monitor -> controller)
resizes the pool each interval, and a shrink preempts whole sequences,
which the engine transparently requeues (their progress is kept: tokens
generated so far become part of the prompt on re-admission).  The
engine declares its pool to the plane at construction and ticks it once
per decode step; all bus/controller wiring stays inside the plane.

Mechanics:

* fixed ``max_batch`` slots; one compiled ``decode_step`` serves every
  mix of sequence progress (per-slot positions),
* admission: a request needs pool blocks for prompt + headroom; denied
  admission leaves it queued,
* each generated token may claim a new block (every ``block_tokens``);
  failure to claim -> self-preemption back to the queue,
* prompt ingestion streams through the same decode step (exact for all
  families, incl. recurrent state).
"""

from __future__ import annotations

import itertools
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.monitor import DeviceMemoryMonitor, MemoryMonitor
from ..core.plane import MemoryPlane, StoreSpec
from ..core.store import KVBlockPool
from ..models import decode as D
from ..models.transformer import Model


@dataclass
class Request:
    rid: int
    prompt: np.ndarray                   # (len,) int32
    max_new_tokens: int
    output: List[int] = field(default_factory=list)
    preemptions: int = 0

    @property
    def done(self) -> bool:
        return len(self.output) >= self.max_new_tokens

    @property
    def tokens_so_far(self) -> np.ndarray:
        return np.concatenate([self.prompt,
                               np.asarray(self.output, np.int32)])


@dataclass
class ServingConfig:
    max_batch: int = 4
    max_len: int = 256
    block_tokens: int = 16
    greedy: bool = True
    cache_dtype: str = "bfloat16"


@dataclass
class _Slot:
    request: Optional[Request] = None
    ingested: int = 0                    # prompt tokens fed so far

    @property
    def free(self) -> bool:
        return self.request is None


class ServingEngine:
    def __init__(self, model: Model, params, cfg: ServingConfig,
                 pool: Optional[KVBlockPool] = None,
                 plane: Optional[MemoryPlane] = None,
                 node: str = "serve0",
                 monitor: Optional[MemoryMonitor] = None,
                 jit: bool = True):
        self.model = model
        self.params = params
        self.cfg = cfg
        kv_bytes = self._block_bytes()
        n_blocks = cfg.max_batch * (cfg.max_len // cfg.block_tokens)
        self.pool = pool or KVBlockPool("kv-pool", n_blocks, kv_bytes)
        self.plane = plane
        self.node = node
        if plane is not None:
            # Declare the pool to the plane: per-chip HBM monitor unless
            # the caller supplies one (tests use a SimulatedMonitor).
            monitor = monitor or DeviceMemoryMonitor(
                jax.devices()[0], node=node,
                storage_used_fn=self.pool.used)
            plane.attach(
                node, monitor,
                stores=(StoreSpec(self.pool, self.pool.total_blocks
                                  * self.pool.block_bytes),))
        self.monitor = monitor
        self.queue: List[Request] = []
        self.finished: Dict[int, Request] = {}
        self.slots = [_Slot() for _ in range(cfg.max_batch)]
        self._rid = itertools.count()
        self.state = D.init_state(model, cfg.max_batch, cfg.max_len,
                                  cache_dtype=cfg.cache_dtype)
        # per-leaf batch axis, found by diffing schema shapes at two batch
        # sizes (stack dims can numerically collide with max_batch)
        s1 = D.state_schema(model, 1, cfg.max_len)
        sN = D.state_schema(model, cfg.max_batch, cfg.max_len)
        from ..models.params import is_leaf as _is_leaf
        self._batch_axis_tree = jax.tree.map(
            lambda a, b: next((i for i, (x, y) in enumerate(
                zip(a.shape, b.shape)) if x != y), None),
            s1, sN, is_leaf=_is_leaf)
        self._step = jax.jit(
            lambda p, s, t: D.decode_step(model, p, s, t)) if jit else (
            lambda p, s, t: D.decode_step(model, p, s, t))
        self.steps = 0

    def _block_bytes(self) -> float:
        cfg = self.model.cfg
        per_tok = 2 * cfg.n_kv_heads * cfg.head_dim * 2   # k+v bf16
        layers = cfg.n_layers
        return float(self.cfg.block_tokens * per_tok * layers)

    # ---- client API ----------------------------------------------------------
    def submit(self, prompt: np.ndarray, max_new_tokens: int) -> int:
        rid = next(self._rid)
        self.queue.append(Request(rid, np.asarray(prompt, np.int32),
                                  max_new_tokens))
        return rid

    def run_until_drained(self, max_steps: int = 100_000) -> Dict[int, Request]:
        while (self.queue or any(not s.free for s in self.slots)):
            self.step()
            if self.steps >= max_steps:
                raise RuntimeError("serving engine did not drain")
        return self.finished

    # ---- engine step ------------------------------------------------------------
    def step(self) -> None:
        self.steps += 1
        self._handle_preemptions()
        self._admit()
        active = [i for i, s in enumerate(self.slots) if not s.free]
        if not active:
            # Still tick the plane: a fully-preempted engine depends on
            # the controller re-granting pool capacity to admit again.
            if self.plane is not None:
                self.plane.tick()
            return
        tokens, feeding = self._next_tokens()
        logits, self.state = self._step(self.params, self.state,
                                        jnp.asarray(tokens))
        self._consume(logits, feeding)
        if self.plane is not None:
            self.plane.tick()

    # ---- internals -----------------------------------------------------------------
    def _handle_preemptions(self) -> None:
        for seq_id in self.pool.drain_preempted():
            slot = self.slots[seq_id]
            if slot.request is not None:
                req = slot.request
                req.preemptions += 1
                self._release_slot(seq_id, requeue=True)

    def _admit(self) -> None:
        for i, slot in enumerate(self.slots):
            if not slot.free or not self.queue:
                continue
            req = self.queue[0]
            need = (len(req.tokens_so_far) // self.cfg.block_tokens) + 1
            if self.pool.num_free_blocks() < need:
                break                      # honor queue order (no starvation)
            for _ in range(need):
                assert self.pool.alloc_block(i) is not None
            self.queue.pop(0)
            slot.request = req
            slot.ingested = 0
            self._reset_slot_state(i)

    def _next_tokens(self):
        """Pick the token each active slot feeds this step."""
        tokens = np.zeros((self.cfg.max_batch, 1), np.int32)
        feeding = {}
        for i, slot in enumerate(self.slots):
            if slot.free:
                continue
            req = slot.request
            seq = req.tokens_so_far
            if slot.ingested < len(seq):
                tokens[i, 0] = seq[slot.ingested]
                feeding[i] = "prompt"
            else:
                feeding[i] = "generate"
                tokens[i, 0] = seq[-1]
        return tokens, feeding

    def _consume(self, logits, feeding) -> None:
        next_tok = np.asarray(jnp.argmax(logits[:, 0], axis=-1))
        for i, mode in feeding.items():
            slot = self.slots[i]
            req = slot.request
            self.pool.touch(i)
            slot.ingested += 1
            if mode == "prompt":
                if slot.ingested < len(req.tokens_so_far):
                    continue
                # prompt done; the argmax after the last prompt token is
                # the first generated token
            req.output.append(int(next_tok[i]))
            if slot.ingested % self.cfg.block_tokens == 0:
                if self.pool.alloc_block(i) is None:
                    req.preemptions += 1
                    self._release_slot(i, requeue=True)
                    continue
            if req.done or slot.ingested >= self.cfg.max_len - 1:
                self._release_slot(i, requeue=False)

    def _reset_slot_state(self, i: int) -> None:
        """Reset one slot: position to 0 and (for recurrent families)
        restore its recurrent state to the init values.  KV cache
        contents need no clearing -- they are masked by position."""
        def reset(leaf, fresh, axis):
            if axis is None:
                return leaf
            idx = [slice(None)] * leaf.ndim
            idx[axis] = i
            return leaf.at[tuple(idx)].set(fresh[tuple(idx)])

        if self.model.cfg.family in ("ssm", "hybrid"):
            if not hasattr(self, "_fresh_state"):
                self._fresh_state = D.init_state(
                    self.model, self.cfg.max_batch, self.cfg.max_len,
                    cache_dtype=self.cfg.cache_dtype)
            self.state = jax.tree.map(reset, self.state,
                                      self._fresh_state,
                                      self._batch_axis_tree)
        else:
            pos = np.asarray(self.state["pos"]).copy()
            pos[i] = 0
            self.state = dict(self.state)
            self.state["pos"] = jnp.asarray(pos)

    def _release_slot(self, i: int, requeue: bool) -> None:
        req = self.slots[i].request
        self.slots[i] = _Slot()
        self.pool.free_seq(i)
        if requeue and req is not None:
            self.queue.insert(0, req)
        elif req is not None:
            self.finished[req.rid] = req

    # ---- metrics ----------------------------------------------------------------
    def stats(self) -> dict:
        return {
            "steps": self.steps,
            "finished": len(self.finished),
            "queued": len(self.queue),
            "active": sum(not s.free for s in self.slots),
            "pool_free_blocks": self.pool.num_free_blocks(),
            "pool_capacity_bytes": self.pool.capacity(),
            "preemptions": sum(r.preemptions
                               for r in self.finished.values())
            + sum(r.preemptions for r in self.queue),
        }
