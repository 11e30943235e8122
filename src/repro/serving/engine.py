"""Continuous-batching gateway engine over the real model.

One `GatewayEngine` owns a fixed pool of decode *slots* backed by a
single shared decode state (KV cache / SSM state) of shape
``(slots, max_len)``. Requests join and retire independently: each slot
carries its own write position, so a request can prefill its prompt while
its neighbours are mid-generation — the per-slot vector `cache_index`
path the model layers grew for exactly this.

The jitted step is memoized through `core.jit_cache` under
``("serve_step", (cfg, slots, max_len))``: every gateway session on the
same (ModelConfig, pool shape) — and every `Session.serve` call — shares
one traced callable. Joins are handled *inside* the trace with a reset
mask that zeroes the joining slot's rows along each state leaf's named
``batch`` axis, so admitting a request never re-triggers compilation.

Sampling happens in the same trace: per-slot temperatures, categorical
when a slot's temperature is positive and argmax otherwise. This is also
where the old `generate()` first-token bug dies — the first sampled
token goes through the same temperature gate as every later one.
"""
from __future__ import annotations

import time
from collections import deque
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import jit_cache
from repro.models import api


def _axis_leaves(axes) -> List[Optional[tuple]]:
    """Flatten an axes tree (leaves are name tuples / None) in the same
    order `tree_flatten` walks the matching value tree."""
    return jax.tree_util.tree_leaves(
        axes, is_leaf=lambda x: x is None or isinstance(x, tuple))


def _reset_by_batch_axis(state, axes, mask):
    """Zero `mask`-selected rows of every state leaf along its named
    ``batch`` axis (family-agnostic: transformer caches carry batch at
    dim 0 or 1 under "layers"; ssm/hybrid leaves likewise)."""
    vals, treedef = jax.tree_util.tree_flatten(state)
    out = []
    for v, ax in zip(vals, _axis_leaves(axes)):
        if ax is not None and "batch" in ax:
            d = ax.index("batch")
            shape = [1] * v.ndim
            shape[d] = v.shape[d]
            v = jnp.where(mask.reshape(shape), jnp.zeros_like(v), v)
        out.append(v)
    return jax.tree_util.tree_unflatten(treedef, out)


def build_step(cfg: ModelConfig, axes):
    """The jitted gateway iteration: reset joining slots, one decode step
    through the cache, then per-slot sampling. `axes` is the decode
    state's axes tree (`api.init_decode_state(...)[1]`)."""
    def f(params, state, toks, pos, reset, temps, key):
        state = _reset_by_batch_axis(state, axes, reset)
        logits, state = api.decode_step(params, cfg, state, toks, pos)
        greedy = jnp.argmax(logits, -1)
        safe = jnp.where(temps > 0, temps, 1.0)
        sampled = jax.random.categorical(key, logits / safe[:, None], -1)
        return jnp.where(temps > 0, sampled, greedy), state
    return jax.jit(f)


class GatewayEngine:
    """Slot-level continuous batching over one model's decode state."""

    def __init__(self, cfg: ModelConfig, params=None, *, slots: int = 4,
                 max_len: int = 64, seed: int = 1):
        if cfg.family == "audio":
            raise ValueError("encoder-only arch has no decode path")
        if params is None:
            params, _ = api.init(cfg, jax.random.PRNGKey(0))
        self.cfg = cfg
        self.params = params
        self.slots = int(slots)
        self.max_len = int(max_len)
        self.state, self._axes = api.init_decode_state(cfg, slots, max_len)
        self.key = jax.random.PRNGKey(seed)

        # per-slot host-side bookkeeping
        self.rid: List[Optional[int]] = [None] * slots
        self._pending: List[deque] = [deque() for _ in range(slots)]
        self._pos = np.zeros(slots, np.int32)       # next write position
        self._last = np.zeros(slots, np.int32)      # last sampled token
        self._temp = np.zeros(slots, np.float32)
        self._budget = np.zeros(slots, np.int64)    # tokens still owed
        self._emitted: List[List[int]] = [[] for _ in range(slots)]
        self._join_mask = np.zeros(slots, bool)     # reset on next step
        self.step_seconds: List[float] = []         # per-iteration wall time

        self._step = jit_cache.cached(
            "serve_step", (cfg, slots, max_len),
            lambda: build_step(cfg, self._axes))

    # ----------------------------------------------------------- admission
    def free_slots(self) -> List[int]:
        return [i for i in range(self.slots) if self.rid[i] is None]

    def busy(self) -> bool:
        return any(r is not None for r in self.rid)

    def join(self, slot: int, rid: int, prompt: Sequence[int],
             max_new: int, temperature: float = 0.0) -> None:
        """Seat request `rid` in `slot`; its prompt prefills token-by-token
        on subsequent `step()` calls while other slots keep decoding."""
        if self.rid[slot] is not None:
            raise ValueError(f"slot {slot} is occupied by rid "
                             f"{self.rid[slot]}")
        prompt = list(int(t) for t in prompt)
        if not prompt:
            raise ValueError(f"rid {rid}: empty prompt")
        if max_new < 1:
            raise ValueError(f"rid {rid}: max_new must be >= 1")
        if len(prompt) + max_new > self.max_len:
            raise ValueError(
                f"rid {rid}: prompt_len {len(prompt)} + max_new {max_new} "
                f"exceeds max_len {self.max_len}")
        self.rid[slot] = rid
        self._pending[slot] = deque(prompt)
        self._pos[slot] = 0
        self._temp[slot] = temperature
        self._budget[slot] = max_new
        self._emitted[slot] = []
        self._join_mask[slot] = True

    def release(self, slot: int) -> List[int]:
        """Evict a slot (retire or external cancel); returns what it had
        emitted so far."""
        out = self._emitted[slot]
        self.rid[slot] = None
        self._pending[slot] = deque()
        self._emitted[slot] = []
        self._budget[slot] = 0
        return out

    # ------------------------------------------------------------- decode
    def step(self) -> List[Dict]:
        """One decode iteration across all occupied slots. Returns one
        event per slot that emitted a token this step:
        ``{"slot", "rid", "token", "done", "tokens"?}`` — prefill steps
        emit nothing for their slot."""
        active = [i for i in range(self.slots) if self.rid[i] is not None]
        if not active:
            return []
        toks = np.zeros(self.slots, np.int32)
        for i in active:
            toks[i] = (self._pending[i].popleft() if self._pending[i]
                       else self._last[i])
        reset = self._join_mask.copy()
        self._join_mask[:] = False
        self.key, sub = jax.random.split(self.key)

        t0 = time.monotonic()
        nxt, self.state = self._step(
            self.params, self.state, jnp.asarray(toks),
            jnp.asarray(self._pos), jnp.asarray(reset),
            jnp.asarray(self._temp), sub)
        nxt = np.asarray(jax.block_until_ready(nxt))
        self.step_seconds.append(time.monotonic() - t0)

        events: List[Dict] = []
        for i in active:
            self._pos[i] += 1
            if self._pending[i]:
                continue                      # still prefilling
            tok = int(nxt[i])
            self._last[i] = tok
            self._emitted[i].append(tok)
            done = len(self._emitted[i]) >= self._budget[i]
            ev = {"slot": i, "rid": self.rid[i], "token": tok,
                  "done": done}
            if done:
                ev["tokens"] = self.release(i)
            events.append(ev)
        return events

    # ------------------------------------------------------------ metrics
    def decode_percentiles_ms(self) -> Dict[str, float]:
        """p50/p95/p99 of per-iteration wall time, milliseconds."""
        if not self.step_seconds:
            return {"p50": 0.0, "p95": 0.0, "p99": 0.0}
        arr = np.asarray(self.step_seconds) * 1e3
        return {"p50": float(np.percentile(arr, 50)),
                "p95": float(np.percentile(arr, 95)),
                "p99": float(np.percentile(arr, 99))}
