"""SAFE chain aggregation — SPMD data plane.

Everything here runs *inside* a ``jax.shard_map`` region that is manual
over the learner axis (``cfg.axis``); one mesh rank = one learner. The
logical chain of the paper's Figure 2 becomes a ``ppermute`` ring.

Two schedules are provided:

  * ``chain_aggregate_sequential`` — paper-faithful Round 1: the full
    masked vector makes n-1 serial hops around the ring. This is the
    baseline recorded in EXPERIMENTS.md §Perf.
  * ``chain_aggregate_pipelined`` — beyond-paper rotated-initiator segment
    pipeline: the vector is split into n segments, segment s is initiated
    (and finally unmasked) by rank s, and all segments move concurrently
    in a ring-reduce schedule. Same privacy invariant (every in-flight
    value is masked by some rank's private R plus the hop pad), but
    ~2V bytes/link instead of (n-1)·V.

Failover: an ``alive`` bitmap (decided *between* rounds by the host
control plane — ``repro.topology.failover.AliveTracker``) compacts the
chain: dead ranks forward-and-repad without contributing, and the
published mean divides by ``popcount(alive)``, matching §5.3's "average
over n-f survivors". The initiator is the first alive rank (§5.4
re-election semantics).

All ring geometry — ppermute pairs, neighbours, initiator election —
comes from ``repro.topology`` (the same objects the discrete-event sim
consumes), so the two planes cannot diverge on topology semantics.

``chain_aggregate_batched`` runs S independent sessions — each with its
own keys, counters, alive bitmap and rotation — through one program; it
is the device substrate of ``serve/agg_engine.AggregationEngine``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.crypto.fixedpoint import FixedPointCodec
from repro.crypto.prf import derive_key, derive_pair_key, keystream_pair_lanes
from repro.core.types import ChainConfig, RoundKeys
from repro.obs.trace import CHAIN_HOP
from repro.topology import elect_initiator_local

# Domain-separation tags for derive_key.
_TAG_INITIATOR_MASK = 0x52  # 'R'
_TAG_HOP_PAD = 0x50  # 'P'


def _ring_hop(x: jax.Array, axis: str, perm) -> jax.Array:
    """One hop of the learner ring: every rank sends ``x`` to its
    successor (a device trace names it ``chain_hop``)."""
    with jax.named_scope(CHAIN_HOP):
        return jax.lax.ppermute(x, axis, perm)


def _hop_pads(keys: RoundKeys, rank, topo, nwords: int, use_pads: bool):
    """Outgoing/incoming one-time pads for this rank's ring edges.

    pad_out is keyed on (rank -> next), pad_in on (prev -> rank); the same
    edge key is derived by both endpoints, so pads cancel hop by hop.
    SAF mode (no hop encryption) uses zero pads — the controller-visible
    traffic is then only protected by the initiator mask, exactly the
    paper's SAF ablation.
    """
    if not use_pads:
        z = jnp.zeros((nwords,), jnp.uint32)
        return z, z
    prv, nxt = topo.neighbors(rank)
    seed = derive_key(keys.provisioning_seed, _TAG_HOP_PAD)
    k_out = derive_pair_key(seed, rank, nxt)
    k_in = derive_pair_key(seed, prv, rank)
    base = jnp.asarray(keys.counter_base, jnp.uint32)
    pad_out = keystream_pair_lanes(k_out, nwords, base)
    pad_in = keystream_pair_lanes(k_in, nwords, base)
    return pad_out, pad_in


def _initiator_mask(keys: RoundKeys, nwords: int, counter_base) -> jax.Array:
    """The single mask R (paper §5.2) — a keystream from this learner's
    private seed. Never shared with the controller or any other learner."""
    k = derive_key(keys.learner_seed, _TAG_INITIATOR_MASK)
    return keystream_pair_lanes(k, nwords, counter_base)


def chain_aggregate_sequential(
    values: jax.Array,
    keys: RoundKeys,
    cfg: ChainConfig,
    alive: jax.Array | None = None,
    weights: jax.Array | None = None,
    rotate: jax.Array | int = 0,
) -> jax.Array:
    """Paper-faithful SAFE Round 1 over one (sub)group ring.

    Args:
      values: f32[V] — this learner's local feature/parameter vector
        (per-rank view inside shard_map).
      keys: RoundKeys (learner_seed must differ per rank).
      cfg: ChainConfig. ``cfg.mode`` must be 'safe' or 'saf'.
      alive: optional f32/bool[n] liveness bitmap (replicated across ranks);
        dead ranks forward-and-repad, contributing nothing.
      weights: optional f32 scalar per rank — weighted averaging (§5.6):
        the aggregate carries (w·x, w) and the published value is
        Σw·x / Σw, without revealing any individual w.
      rotate: per-round initiator rotation (paper §8: "randomize the order
        between each round to limit the likelihood of two colluding nodes
        being able to get useful data from intermediaries on a consistent
        basis"). The ring edges (and hop keys) are fixed; the initiator
        role starts ``rotate`` positions later each round.

    Returns:
      f32[V] — the (weighted) mean over alive learners, identical on every
      rank (the paper's post_average/get_average distribution).
    """
    assert cfg.mode in ("safe", "saf"), cfg.mode
    topo = cfg.topology
    n, m = cfg.num_learners, cfg.group_size
    axis = cfg.axis
    rank = jax.lax.axis_index(axis)
    codec = FixedPointCodec(cfg.scale_bits)

    if alive is None:
        alive = jnp.ones((n,), jnp.float32)
    alive = jnp.asarray(alive, jnp.float32)
    my_alive = alive[rank]

    if cfg.weighted:
        w = jnp.asarray(1.0 if weights is None else weights, jnp.float32)
        payload = jnp.concatenate([values * w, jnp.array([w], values.dtype)])
    else:
        payload = values
    nwords = payload.shape[0]

    ev = codec.encode(payload) * my_alive.astype(jnp.uint32)
    pad_out, pad_in = _hop_pads(keys, rank, topo, nwords, cfg.mode == "safe")
    R = _initiator_mask(keys, nwords, keys.counter_base)

    # Initiator of each subgroup ring: shared election formula from the
    # topology layer (§5.4 re-election + §8 round-order randomization).
    g0 = topo.group_start(rank)
    group_alive = jax.lax.dynamic_slice(alive, (g0,), (m,))
    init_local = elect_initiator_local(group_alive, rotate, xp=jnp)
    init_rank = g0 + init_local
    is_init = rank == init_rank

    # Hop 0: the initiator posts enc<x_init + R> to its successor.
    x = jnp.where(is_init, ev + R + pad_out, jnp.zeros_like(ev))

    perm = topo.ring_permutation()

    def hop(t, x):
        x = _ring_hop(x, axis, perm)
        # The rank t local-steps after the initiator combines now:
        active = rank == g0 + (init_local + t) % m
        delta = ev - pad_in + pad_out  # decrypt, add local, re-encrypt
        return x + jnp.where(active, delta, jnp.zeros_like(ev))

    for t in range(1, m):  # unrolled: XLA sees, and overlaps, every hop
        x = hop(t, x)

    # Final hop back to the initiator, which unmasks.
    x = _ring_hop(x, axis, perm)
    total = x - pad_in - R  # Σ enc(x_i) over the group, exact in Z/2^32Z

    count = jnp.sum(group_alive)
    if cfg.weighted:
        s = codec.decode(total)
        group_avg = s[:-1] / jnp.maximum(s[-1], 1e-12)
    else:
        group_avg = codec.decode_mean(total, jnp.maximum(count, 1.0))

    # Only the initiator holds the real average — broadcast it (the
    # paper's post_average / get_average round-trip).
    return _publish(group_avg, is_init, cfg, broadcast=True)


def chain_aggregate_pipelined(
    values: jax.Array,
    keys: RoundKeys,
    cfg: ChainConfig,
    alive: jax.Array | None = None,
    weights: jax.Array | None = None,
) -> jax.Array:
    """Beyond-paper rotated-initiator segment pipeline (DESIGN.md §8).

    The vector is padded to m segments (m = group size); segment s is
    initiated, masked (R_s from rank s's private seed) and finally
    unmasked by local rank s. All m segments traverse the ring
    concurrently in a reduce-scatter schedule, then an all_gather
    republishes the full mean. Privacy invariant unchanged: every value a
    non-owner sees is offset by another rank's private mask.
    """
    assert cfg.mode in ("safe", "saf"), cfg.mode
    topo = cfg.topology
    n, m = cfg.num_learners, cfg.group_size
    axis = cfg.axis
    rank = jax.lax.axis_index(axis)
    codec = FixedPointCodec(cfg.scale_bits)

    if alive is None:
        alive = jnp.ones((n,), jnp.float32)
    alive = jnp.asarray(alive, jnp.float32)
    my_alive = alive[rank]

    if cfg.weighted:
        w = jnp.asarray(1.0 if weights is None else weights, jnp.float32)
        payload = jnp.concatenate([values * w, jnp.array([w], values.dtype)])
    else:
        payload = values
    V = payload.shape[0]
    seg = -(-V // m)  # ceil
    pad_len = seg * m - V
    payload = jnp.pad(payload, (0, pad_len))

    ev = (codec.encode(payload) * my_alive.astype(jnp.uint32)).reshape(m, seg)

    g0 = topo.group_start(rank)
    lrank = topo.local_index(rank)
    group_alive = jax.lax.dynamic_slice(alive, (g0,), (m,))

    # Per-(edge, segment) pads: counter offset s*seg keeps streams disjoint.
    prv, nxt = topo.neighbors(rank)
    use_pads = cfg.mode == "safe"
    base = jnp.asarray(keys.counter_base, jnp.uint32)
    if use_pads:
        seedp = derive_key(keys.provisioning_seed, _TAG_HOP_PAD)
        k_out = derive_pair_key(seedp, rank, nxt)
        k_in = derive_pair_key(seedp, prv, rank)
        pads_out = keystream_pair_lanes(k_out, m * seg, base).reshape(m, seg)
        pads_in = keystream_pair_lanes(k_in, m * seg, base).reshape(m, seg)
    else:
        pads_out = pads_in = jnp.zeros((m, seg), jnp.uint32)

    # This rank's own segment mask R_lrank (it is the initiator of segment
    # lrank on its subgroup ring).
    R_own = _initiator_mask(keys, seg, base)

    perm = topo.ring_permutation()

    # Step 0: every rank starts its own segment's chain.
    s = lrank
    c = ev[s] + R_own + pads_out[s]

    def step(t, c):
        c = _ring_hop(c, axis, perm)
        s = (lrank - t) % m  # segment id now resident on this rank
        return c - pads_in[s] + ev[s] + pads_out[s]

    for t in range(1, m):
        c = step(t, c)

    # One final hop returns segment lrank to its initiator, which unmasks.
    c = _ring_hop(c, axis, perm)
    total_seg = c - pads_in[lrank] - R_own  # Σ_i enc(x_i)[segment lrank]

    # Republish: all_gather the unmasked segment sums (aggregates are
    # public by protocol — this is the paper's average distribution).
    total = jax.lax.all_gather(total_seg, axis, tiled=True)
    # all_gather over the full axis concatenates all n ranks; with
    # subgroups each group's segments repeat per group — slice ours.
    if cfg.subgroups > 1:
        total = jax.lax.dynamic_slice(total, (g0 * seg,), (m * seg,))
    total = total[: m * seg]

    count = jnp.sum(group_alive)
    if cfg.weighted:
        sdec = codec.decode(total)[:V]
        group_avg = sdec[:-1] / jnp.maximum(sdec[-1], 1e-12)
    else:
        group_avg = codec.decode_mean(total[:V], jnp.maximum(count, 1.0))
        group_avg = group_avg[: values.shape[0]]

    # The all_gather already distributed the group result to every member,
    # so only cross-group averaging (not a broadcast) is needed.
    is_init = rank == g0  # publication anchor for cross-group averaging
    return _publish(group_avg, is_init, cfg, broadcast=False)


def _publish(group_avg: jax.Array, is_init, cfg: ChainConfig, *, broadcast: bool) -> jax.Array:
    """Cross-group and cross-pod publication (paper §5.5, §5.10).

    With g subgroups the controller averages the g group averages; with a
    pod axis, child controllers post group averages to the parent (§5.10)
    — a plain mean over the pod axis, no encryption needed since group
    averages are already anonymized over >= 3 learners.

    Args:
      broadcast: True when ``group_avg`` is only valid on the group
        initiator (sequential schedule) and must be distributed; False
        when every group member already holds it (pipelined schedule).
    """
    if cfg.subgroups > 1 or broadcast:
        # Each group's initiator posts its average; everyone receives the
        # mean of the g posted averages (g = 1 reduces to a broadcast).
        contrib = jnp.where(is_init, group_avg, jnp.zeros_like(group_avg))
        avg = jax.lax.psum(contrib, cfg.axis) / cfg.subgroups
    else:
        avg = group_avg
    if cfg.pod_axis is not None:
        avg = jax.lax.pmean(avg, cfg.pod_axis)
    return avg


def chain_aggregate_batched(
    values: jax.Array,
    prov_seeds: jax.Array,
    learner_seeds: jax.Array,
    counter_bases: jax.Array,
    cfg: ChainConfig,
    alive: jax.Array,
    weights: jax.Array | None = None,
    rotate: jax.Array | None = None,
) -> jax.Array:
    """S independent SAFE rounds through one program (per-rank view).

    Each session s runs the exact arithmetic of
    ``chain_aggregate_sequential`` — its own derived keys, counter space,
    alive bitmap and initiator rotation — so session s's published mean
    is bit-identical to a standalone single-session run with the same
    inputs (asserted by tests/test_session_engine.py). The batch is a
    ``vmap`` over the session dim: the hop structure (ppermute schedule)
    is shared, so S rounds cost one collective per hop instead of S.

    Args:
      values: f32[S, V] — this rank's vector for each session.
      prov_seeds: uint32[S, 2] — per-session *derived* provisioning key
        (the output of ``derive_key(seed_words, domain, epoch)``, i.e. exactly
        what ``make_round_keys`` puts in ``RoundKeys.provisioning_seed``).
      learner_seeds: uint32[S, 2] — per-session per-rank private seed
        (``RoundKeys.learner_seed``).
      counter_bases: uint32[S] — per-session fresh counter base.
      cfg: shared ChainConfig (one topology/mode for the whole batch —
        the engine's slots are homogeneous, like ServeEngine's).
      alive: f32[S, n] per-session liveness bitmaps.
      weights: optional f32[S] per-session weight of this rank.
      rotate: optional i32[S] per-session initiator rotation.

    Returns:
      f32[S, V] published (weighted) means, identical on every rank.
    """
    S = values.shape[0]
    if rotate is None:
        rotate = jnp.zeros((S,), jnp.int32)
    if weights is None and cfg.weighted:
        weights = jnp.ones((S,), jnp.float32)

    def one(v, prov, learner, ctr, al, rot, w):
        keys = RoundKeys(provisioning_seed=prov, learner_seed=learner,
                         counter_base=ctr)
        return chain_aggregate_sequential(v, keys, cfg, alive=al,
                                          weights=w, rotate=rot)

    if cfg.weighted:
        return jax.vmap(one)(values, prov_seeds, learner_seeds,
                             counter_bases, alive, rotate, weights)
    return jax.vmap(
        lambda v, p, l, c, a, r: one(v, p, l, c, a, r, None)
    )(values, prov_seeds, learner_seeds, counter_bases, alive, rotate)
