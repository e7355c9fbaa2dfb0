"""Batched serving engine: continuous prefill + decode.

``make_serve_step`` builds the jitted one-token decode step: ONE new
token against a KV cache (or recurrent state) of ``seq_len``.

``ServeEngine`` is the host-side loop the serving example drives: a
fixed-size batch of slots, each slot holding one request's cache; new
requests are prefilled into free slots, finished ones evicted. (Slot
caches share one stacked cache pytree — eviction is a masked reset, so
the decode step stays a single compiled program.)
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.transformer import Model


def cache_pspecs(cache_abs, batch_sharded: bool,
                 seq_axis: Optional[str] = None, model_size: int = 1):
    """PartitionSpecs for a stacked decode cache (pytree-parallel).

    batch_sharded: shard the batch dim over 'data' (decode_32k).
    seq_axis: shard the attention-cache sequence dim instead (long_500k,
    batch=1 — the beyond-paper sequence-parallel KV layout).
    Attention k/v are [n_units, B, S_c, n_kv, hd]; recurrent states
    [n_units, B, H, ...]; pos [n_units, B]. Head dims shard over 'model'
    only when divisible (GQA kv counts are often < the TP degree)."""
    def heads(leaf, dim):
        return "model" if np.shape(leaf)[dim] % max(model_size, 1) == 0 else None

    def spec_for(leaf):
        nd = np.ndim(leaf)
        if nd == 5:  # attention kv
            if batch_sharded:
                return P(None, "data", None, heads(leaf, 3), None)
            if seq_axis:
                return P(None, None, seq_axis, heads(leaf, 3), None)
            return P(None, None, None, heads(leaf, 3), None)
        if nd == 4:  # mamba2 / rwkv6 state [U, B, H, ...]
            return P(None, "data" if batch_sharded else None,
                     heads(leaf, 2), None)
        if nd == 3:  # rwkv prev [U, B, d]
            return P(None, "data" if batch_sharded else None, None)
        if nd == 2:  # pos [U, B]
            return P(None, "data") if batch_sharded else P()
        return P()

    return jax.tree.map(spec_for, cache_abs)


def make_serve_step(model: Model, mesh: Optional[Mesh] = None):
    """Jitted (params, tokens, cache) -> (logits, cache) one-token step."""
    step = jax.jit(model.decode_step)

    def serve_step(params, tokens, cache):
        if mesh is not None:
            with jax.set_mesh(mesh):
                return step(params, tokens, cache)
        return step(params, tokens, cache)

    return serve_step


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray  # int32[S]
    max_new: int = 32
    generated: list = dataclasses.field(default_factory=list)
    done: bool = False


class ServeEngine:
    """Host-side batched serving loop (the serving example's core)."""

    def __init__(self, model: Model, params, batch_slots: int = 4,
                 max_seq: int = 512, temperature: float = 0.0, seed: int = 0):
        self.model = model
        self.params = params
        self.slots = batch_slots
        self.max_seq = max_seq
        self.temperature = temperature
        self.rng = np.random.RandomState(seed)
        self.cache = model.init_cache(batch_slots, max_seq, prefilled=False)
        self.slot_req: list[Optional[Request]] = [None] * batch_slots
        self.queue: list[Request] = []
        self._decode = jax.jit(model.decode_step)
        self._prefill = jax.jit(model.prefill)
        self.steps = 0

    def submit(self, req: Request) -> None:
        self.queue.append(req)

    def _free_slot(self) -> Optional[int]:
        for i, r in enumerate(self.slot_req):
            if r is None:
                return i
        return None

    def _admit(self) -> None:
        while self.queue and (slot := self._free_slot()) is not None:
            req = self.queue.pop(0)
            self.slot_req[slot] = req
            # prefill this request alone, then splice its cache into the slot
            one_cache = self.model.init_cache(1, self.max_seq, prefilled=False)
            toks = jnp.asarray(req.prompt[None, :], jnp.int32)
            logits, one_cache = self._prefill(self.params, toks, cache=one_cache)
            req.generated = [int(jnp.argmax(logits[0]))]
            self.cache = jax.tree.map(
                lambda full, one: full.at[:, slot].set(one[:, 0]),
                self.cache, one_cache)

    def _sample(self, logits: jax.Array) -> np.ndarray:
        if self.temperature <= 0:
            return np.asarray(jnp.argmax(logits, axis=-1))
        p = np.asarray(jax.nn.softmax(logits / self.temperature, axis=-1))
        return np.array([self.rng.choice(p.shape[-1], p=row) for row in p])

    def step(self) -> None:
        """Admit + one decode step for all active slots."""
        self._admit()
        active = [r for r in self.slot_req if r is not None]
        if not active:
            return
        last = np.zeros((self.slots,), np.int32)
        for i, r in enumerate(self.slot_req):
            if r is not None and r.generated:
                last[i] = r.generated[-1]
        logits, self.cache = self._decode(self.params,
                                          jnp.asarray(last), self.cache)
        nxt = self._sample(logits)
        self.steps += 1
        for i, r in enumerate(self.slot_req):
            if r is None:
                continue
            r.generated.append(int(nxt[i]))
            if len(r.generated) >= r.max_new:
                r.done = True
                self.slot_req[i] = None

    def run_until_done(self, max_steps: int = 10_000) -> None:
        while (self.queue or any(self.slot_req)) and self.steps < max_steps:
            self.step()
