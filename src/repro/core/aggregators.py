"""Aggregator interface — SAFE and baselines as pluggable components.

``SecureAggregator`` is the first-class framework object: the train
step, the federated trainer and the benchmarks all consume it. The per-rank
``aggregate`` method composes inside any shard_map region that is manual
over the learner axis; ``aggregate_sharded`` is a standalone jit entry
point for tests/benchmarks.

Key provisioning model (DESIGN.md §6): a ``provisioning_seed`` models the
Round-0 out-of-band exchange (pairwise hop keys are KDF(provisioning,
i, j)); each learner's private seed is KDF(learner_master, rank). In a
real deployment learner_master never leaves the learner — here it is a
simulation input, and the privacy argument is carried by the control-plane
tests (controller never observes an unmasked value).

Key epochs (Round-0 rotation): both seeds are folded with a key epoch, a
traced uint32, so each epoch has fresh pair and initiator keys and with
them a fresh 2**32 words of counter space. ``reserve_round`` hands out
``(epoch, base)`` and opens the next epoch, at base 0, when a reservation
would not fit in what is left of the current one. A compiled program
takes the epoch as an input, so a rotation recompiles nothing.
"""
from __future__ import annotations

import copy
import dataclasses
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.flatten_util import ravel_pytree
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.bon import bon_aggregate
from repro.core.chain import chain_aggregate_pipelined, chain_aggregate_sequential
from repro.core.insec import insec_aggregate
from repro.core.types import ChainConfig, RoundKeys
from repro.crypto.prf import RoundCounter, derive_key
from repro.obs.metrics import MetricsRegistry

#: host counter: key epochs opened after the first, i.e. key rotations
KEY_EPOCHS = "safe_key_epochs_total"


class Reservation(NamedTuple):
    """Where one aggregation round's pads live: the key epoch and the
    first counter word of the round's range in that epoch."""
    epoch: int
    base: int


def make_round_keys(
    provisioning_seed: int,
    learner_master: int,
    counter_base: int,
    rank: Optional[jax.Array] = None,
    axis: str = "data",
    domain: int = 0,
    epoch: int | jax.Array = 0,
) -> RoundKeys:
    """Build per-rank RoundKeys inside a shard_map region.

    ``domain`` separates keystreams when one round aggregates multiple
    vectors (leaf-wise aggregation of a parameter tree): each domain gets
    independent derived keys, so 32-bit counter space is per-leaf.
    ``epoch`` is the key epoch (uint32, traced or not): it is folded into
    both seeds, so every epoch's pads are independent of every other's."""
    if rank is None:
        rank = jax.lax.axis_index(axis)
    epoch = jnp.asarray(epoch, jnp.uint32)
    prov = derive_key(jnp.array([provisioning_seed & 0xFFFFFFFF,
                                 (provisioning_seed >> 32) & 0xFFFFFFFF],
                                dtype=jnp.uint32), domain, epoch)
    master = jnp.array([learner_master & 0xFFFFFFFF,
                        (learner_master >> 32) & 0xFFFFFFFF], dtype=jnp.uint32)
    learner = derive_key(derive_key(master, domain, epoch), rank)
    return RoundKeys(provisioning_seed=prov, learner_seed=learner,
                     counter_base=jnp.asarray(counter_base, jnp.uint32))


def _reserve(epoch: int, counters: RoundCounter,
             nwords: int) -> tuple[int, RoundCounter, int]:
    """(epoch, counters, base) after reserving ``nwords`` in ``epoch``, or
    at base 0 of the next epoch when they do not fit in what is left."""
    nwords = int(nwords)
    if nwords > RoundCounter.LIMIT:
        raise OverflowError(f"a round of {nwords} words exceeds one key "
                            f"epoch's 2**32 counter words")
    if nwords > counters.remaining:
        if epoch + 1 >= 2**32:
            raise OverflowError("key epochs exhausted")
        epoch, counters = epoch + 1, RoundCounter()
    return epoch, counters, counters.reserve(nwords)


@dataclasses.dataclass
class SecureAggregator:
    """Pluggable secure-mean over a mesh axis.

    mode is taken from ``cfg.mode``: insec | saf | safe | bon;
    ``cfg.pipelined`` selects the beyond-paper schedule for saf/safe.
    """

    cfg: ChainConfig
    provisioning_seed: int = 0xC0FFEE
    learner_master: int = 0x5EED
    #: host counters; ``KEY_EPOCHS`` counts the rotations
    metrics: MetricsRegistry = dataclasses.field(
        default_factory=MetricsRegistry)
    _epoch: int = 0
    _counters: RoundCounter = dataclasses.field(default_factory=RoundCounter)

    # ---- host-side key/counter management -------------------------------
    def reserve_round(self, nwords: int) -> Reservation:
        """Reserve fresh counter space for one aggregation round.

        SAFE uses one pad word per payload word per edge; BON uses one per
        pair. A single monotone space sized by the worst case keeps the
        no-reuse invariant simple. When ``nwords`` do not fit in what is
        left of the current key epoch, the next epoch opens (Round-0 key
        rotation) and the round starts at its base 0: a range never
        wraps, so no pad is ever used twice.
        """
        epoch, self._counters, base = _reserve(self._epoch, self._counters,
                                               nwords)
        if epoch != self._epoch:
            self.metrics.counter(KEY_EPOCHS).inc()
        self._epoch = epoch
        return Reservation(epoch, base)

    def key_state_after(self, rounds: int, nwords: int) -> dict:
        """The key epoch and its first unreserved counter word as they
        will be after ``rounds`` more reservations of ``nwords``. A
        checkpoint saves this, with the rounds until the next checkpoint:
        a run resumed from it (``resume``) starts past every counter the
        saving run can have used, however far it got."""
        epoch, counters = self._epoch, copy.deepcopy(self._counters)
        for _ in range(int(rounds)):
            epoch, counters, _ = _reserve(epoch, counters, nwords)
        return {"key_epoch": epoch,
                "counter_next": RoundCounter.LIMIT - counters.remaining}

    def resume(self, key_epoch: int, counter_next: int) -> None:
        """Continue reserving from a saved ``key_state_after``."""
        if not 0 <= int(key_epoch) < 2**32:
            raise ValueError(f"key epoch {key_epoch} is not a uint32")
        counters = RoundCounter()
        counters.reserve(int(counter_next))
        self._epoch, self._counters = int(key_epoch), counters

    # ---- per-rank (inside shard_map) ------------------------------------
    def aggregate(
        self,
        values: jax.Array,
        counter_base: int | jax.Array = 0,
        alive: Optional[jax.Array] = None,
        weights: Optional[jax.Array] = None,
        domain: int = 0,
        rotate: jax.Array | int = 0,
        epoch: int | jax.Array = 0,
    ) -> jax.Array:
        """Secure mean of per-rank f32[V] over cfg.axis. Call inside
        shard_map (manual over cfg.axis). ``rotate`` shifts the initiator
        role per round (paper §8 collusion mitigation); ``epoch`` and
        ``counter_base`` are a ``reserve_round`` reservation."""
        keys = make_round_keys(self.provisioning_seed, self.learner_master,
                               counter_base, axis=self.cfg.axis,
                               domain=domain, epoch=epoch)
        mode = self.cfg.mode
        if mode == "insec":
            return insec_aggregate(values, self.cfg, alive, weights)
        if mode == "bon":
            return bon_aggregate(values, keys, self.cfg, alive)
        if self.cfg.pipelined:
            return chain_aggregate_pipelined(values, keys, self.cfg, alive,
                                             weights)
        return chain_aggregate_sequential(values, keys, self.cfg, alive,
                                          weights, rotate=rotate)

    def aggregate_tree(
        self,
        tree: Any,
        counter_base: int | jax.Array = 0,
        alive: Optional[jax.Array] = None,
        weights: Optional[jax.Array] = None,
    ) -> Any:
        """Secure mean of an arbitrary pytree (gradients / model deltas)."""
        flat, unravel = ravel_pytree(tree)
        avg = self.aggregate(flat.astype(jnp.float32), counter_base, alive, weights)
        return unravel(avg)

    # ---- standalone entry point ------------------------------------------
    def aggregate_sharded(
        self,
        mesh: Mesh,
        global_values: jax.Array,
        counter_base: int | jax.Array = 0,
        alive: Optional[jax.Array] = None,
        weights: Optional[jax.Array] = None,
    ) -> jax.Array:
        """Aggregate a [n, V] learner-major matrix sharded over cfg.axis.

        Returns the [V] published mean (identical on every learner —
        asserted by out_specs replication).
        """
        cfg = self.cfg
        if alive is None:
            alive = jnp.ones((cfg.num_learners,), jnp.float32)
        if weights is None:
            weights = jnp.ones((cfg.num_learners,), jnp.float32)

        def per_rank(vals, alive_, w):
            return self.aggregate(
                vals.reshape(vals.shape[-1]), counter_base, alive_, w.reshape(())
            )

        manual = {cfg.axis} | ({cfg.pod_axis} if cfg.pod_axis else set())
        shard_fn = jax.shard_map(
            per_rank,
            mesh=mesh,
            in_specs=(P(cfg.axis), P(), P(cfg.axis)),
            out_specs=P(),
            axis_names=frozenset(manual),
            check_vma=False,
        )
        with jax.set_mesh(mesh):
            return jax.jit(shard_fn)(global_values, alive, weights)


_REGISTRY: dict[str, Callable[..., ChainConfig]] = {}


def make_aggregator(
    mode: str,
    num_learners: int,
    axis: str = "data",
    *,
    pipelined: bool = False,
    subgroups: int = 1,
    weighted: bool = False,
    pod_axis: Optional[str] = None,
    scale_bits: int = 16,
    provisioning_seed: int = 0xC0FFEE,
    learner_master: int = 0x5EED,
) -> SecureAggregator:
    """Factory used by configs / CLI (``--aggregator safe`` etc.)."""
    cfg = ChainConfig(
        axis=axis,
        num_learners=num_learners,
        scale_bits=scale_bits,
        mode=mode,
        pipelined=pipelined,
        subgroups=subgroups,
        weighted=weighted,
        pod_axis=pod_axis,
    )
    return SecureAggregator(cfg, provisioning_seed, learner_master)
