"""Multi-session aggregation engine: S concurrent SAFE rounds, one program.

The aggregation sibling of :class:`~repro.serve.engine.ServeEngine`: a
fixed batch of S *slots*, each holding one tenant's
:class:`~repro.core.session.AggSession`. Every ``step()`` admits queued
sessions into free slots and runs ONE compiled shard_map program that
advances every occupied slot by one aggregation round —
``chain_aggregate_batched`` vmaps the session dim, so S rounds share one
ppermute schedule (one collective per hop instead of S) and one XLA
dispatch. Finished sessions are evicted; empty slots ride along masked
out (their published output is discarded).

Per-slot independence is total: keys, counter spaces, alive bitmaps and
initiator rotations are per-session, and the batched arithmetic is
bit-identical to S standalone single-session runs (asserted in
tests/test_session_engine.py). Slots are homogeneous in (n, V, mode,
topology) — one compiled program — exactly as ServeEngine slots share
one stacked cache shape.

Throughput: benchmarks/multi_session.py measures rounds/sec vs. the
unbatched loop at S ∈ {1, 8, 32}.

Behind the wire broker (``net/broker.py`` ``submit_session`` /
``wait_session``, docs/PROTOCOL.md §7) an engine instance serves many
TCP tenants; those ops still carry whole sessions in single frames —
chunk-streamed engine submissions are a ROADMAP open item.
"""
from __future__ import annotations

from typing import Callable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.chain import chain_aggregate_batched
from repro.core.session import AggSession
from repro.core.types import ChainConfig
from repro.crypto.prf import derive_key


class AggregationEngine:
    """Slot-based scheduler batching S SAFE sessions through one program.

    Args:
      mesh: mesh whose ``cfg.axis`` dimension is the learner axis.
      cfg: shared ChainConfig (mode must be 'safe' or 'saf'; the
        sequential schedule is the batched substrate).
      slots: S — max concurrent sessions per step.
      payload_words: V — per-learner vector length every session uses.
    """

    def __init__(self, mesh: Mesh, cfg: ChainConfig, slots: int = 8,
                 payload_words: int = 1024):
        if cfg.mode not in ("safe", "saf"):
            raise ValueError("AggregationEngine batches the chain modes "
                             f"('safe'/'saf'), got {cfg.mode!r}")
        self.mesh = mesh
        self.cfg = cfg
        self.slots = slots
        self.V = payload_words
        self.n = cfg.num_learners
        # counter words one round consumes (weighted carries Σw as an
        # extra ring word) — sessions advance their counter by this much
        self.words_per_round = self.V + 1 if cfg.weighted else self.V
        self.slot_sessions: List[Optional[AggSession]] = [None] * slots
        self.queue: List[AggSession] = []
        self.steps = 0
        self.rounds_completed = 0
        self._next_sid = 0
        #: optional completion hook: called synchronously from step()
        #: with each AggSession the moment it finishes its last round
        #: (used by net/broker.py to resolve wire-side wait_session
        #: long-polls without scanning slots).
        self.on_complete: Optional[Callable[[AggSession], None]] = None
        self._vals_sharding = NamedSharding(mesh, P(None, cfg.axis))
        self._program = self._build_program()

    # ---- compiled program ------------------------------------------------
    def _build_program(self):
        cfg, S = self.cfg, self.slots

        def per_rank(vals, prov_w, master_w, ctrs, alive, wts, rots):
            # vals arrives [S, 1, V] (this rank's slice of the learner dim)
            vals = vals.reshape(S, self.V)
            rank = jax.lax.axis_index(cfg.axis)
            # per-session key derivation — the exact make_round_keys
            # chain (domain 0, key epoch 0), vmapped over the session dim
            prov_d = jax.vmap(lambda w: derive_key(w, 0, 0))(prov_w)
            learner_d = jax.vmap(
                lambda w: derive_key(derive_key(w, 0, 0), rank))(master_w)
            w_r = wts[:, rank] if cfg.weighted else None
            return chain_aggregate_batched(
                vals, prov_d, learner_d, ctrs, cfg, alive,
                weights=w_r, rotate=rots)

        shard_fn = jax.shard_map(
            per_rank,
            mesh=self.mesh,
            in_specs=(P(None, cfg.axis), P(), P(), P(), P(), P(), P()),
            out_specs=P(),
            axis_names=frozenset({cfg.axis}),
            check_vma=False,
        )
        return jax.jit(shard_fn)

    # ---- host-side scheduling -------------------------------------------
    def submit(self, values: np.ndarray, *, rounds: int = 1,
               provisioning_seed: int = 0xC0FFEE,
               learner_master: int = 0x5EED,
               alive: Optional[np.ndarray] = None,
               weights: Optional[np.ndarray] = None,
               rotate0: int = 0) -> AggSession:
        """Queue a session. values: f32[n, V]."""
        values = np.asarray(values, np.float32)
        if values.shape != (self.n, self.V):
            raise ValueError(
                f"session shape {values.shape} != engine slots' "
                f"({self.n}, {self.V})")
        if rounds < 1:
            raise ValueError(f"rounds must be >= 1, got {rounds}")
        sess = AggSession(self._next_sid, values, provisioning_seed,
                          learner_master, rounds, alive, weights, rotate0)
        self._next_sid += 1
        self.queue.append(sess)
        return sess

    def _admit(self) -> None:
        for i, s in enumerate(self.slot_sessions):
            if s is None and self.queue:
                self.slot_sessions[i] = self.queue.pop(0)

    @property
    def active(self) -> int:
        return sum(s is not None for s in self.slot_sessions)

    def step(self) -> int:
        """Admit + advance every occupied slot one round. Returns the
        number of session-rounds completed this step."""
        self._admit()
        if self.active == 0:
            return 0
        S, n, V = self.slots, self.n, self.V
        vals = np.zeros((S, n, V), np.float32)
        prov_w = np.zeros((S, 2), np.uint32)
        master_w = np.zeros((S, 2), np.uint32)
        ctrs = np.zeros((S,), np.uint32)
        alive = np.ones((S, n), np.float32)
        wts = np.ones((S, n), np.float32)
        rots = np.zeros((S,), np.int32)
        for i, sess in enumerate(self.slot_sessions):
            if sess is None:
                continue  # masked slot: all-alive zeros, result discarded
            vals[i] = sess.values
            prov_w[i], master_w[i] = sess.key_words()
            rots[i] = sess.rotate
            ctrs[i] = np.uint32(sess.reserve_counter(self.words_per_round)
                                & 0xFFFFFFFF)
            alive[i] = sess.alive
            wts[i] = sess.weights

        # place each rank's [S, 1, V] slice on its own device: staging the
        # whole [S, n, V] on one device first takes n× its share of HBM
        vals = jax.device_put(vals, self._vals_sharding)
        with jax.set_mesh(self.mesh):
            out = self._program(vals, jnp.asarray(prov_w),
                                jnp.asarray(master_w), jnp.asarray(ctrs),
                                jnp.asarray(alive), jnp.asarray(wts),
                                jnp.asarray(rots))
        out = np.asarray(jax.block_until_ready(out))

        completed = 0
        for i, sess in enumerate(self.slot_sessions):
            if sess is None:
                continue
            sess.record_result(out[i])
            completed += 1
            if sess.done:
                self.slot_sessions[i] = None
                if self.on_complete is not None:
                    self.on_complete(sess)
        self.steps += 1
        self.rounds_completed += completed
        return completed

    def run_until_done(self, max_steps: int = 10_000) -> None:
        while (self.queue or self.active) and self.steps < max_steps:
            self.step()
