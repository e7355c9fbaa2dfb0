"""Threefry-2x32 counter-mode PRF in pure JAX.

This is the cipher underlying ``jax.random`` (Salmon et al., "Parallel
random numbers: as easy as 1, 2, 3", SC'11), re-implemented here so that

  * the secure-aggregation core has a self-contained, auditable keystream
    generator (we do not depend on jax.random internals or versioning),
  * the Pallas kernels in ``repro.kernels`` have a bit-exact pure-jnp
    oracle to validate against.

SAFE usage (DESIGN.md §2): hop "encryption" between chain neighbours is a
one-time pad ``cipher = plain + PRF(k_pair, counter)  (mod 2**32)``, the
TPU-native form of the paper's pre-negotiated symmetric-key mode (§5.8).
The initiator mask R (§5.2) is a keystream from the initiator's private
seed. All arithmetic is uint32 so the masking is exact.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.trace import KEYSTREAM

# Threefry-2x32 rotation schedule (8 distinct rotations, reused over 20 rounds).
_ROTATIONS = ((13, 15, 26, 6), (17, 29, 16, 24))
# Threefish key-schedule parity constant for 32-bit words.
_PARITY = np.uint32(0x1BD11BDA)


def _rotl32(x: jax.Array, d: int) -> jax.Array:
    """Rotate-left for uint32 lanes."""
    return (x << d) | (x >> (32 - d))


def threefry2x32(key: jax.Array, x0: jax.Array, x1: jax.Array):
    """Threefry-2x32, 20 rounds.

    Args:
      key: uint32[2] cipher key (k0, k1).
      x0, x1: uint32 counter words, broadcastable to a common shape.

    Returns:
      (y0, y1): uint32 keystream words, same shape as the broadcast inputs.
    """
    key = jnp.asarray(key, jnp.uint32)
    x0 = jnp.asarray(x0, jnp.uint32)
    x1 = jnp.asarray(x1, jnp.uint32)
    ks0, ks1 = key[0], key[1]
    ks2 = ks0 ^ ks1 ^ _PARITY

    x0 = x0 + ks0
    x1 = x1 + ks1
    ks = (ks0, ks1, ks2)
    for i in range(5):
        for r in _ROTATIONS[i % 2]:
            x0 = x0 + x1
            x1 = _rotl32(x1, r)
            x1 = x1 ^ x0
        x0 = x0 + ks[(i + 1) % 3]
        x1 = x1 + ks[(i + 2) % 3] + np.uint32(i + 1)
    return x0, x1


def keystream_pair_lanes(key: jax.Array, n: int, counter_base: jax.Array | int = 0) -> jax.Array:
    """Generate ``n`` uint32 keystream words from both Threefry output lanes.

    Block ``counter_base + b`` yields words ``(2b, 2b+1)``, so streams of
    successive rounds never overlap when the caller advances
    ``counter_base`` by at least ``ceil(n / 2)`` blocks (see
    ``RoundCounter``). This is the schedule the Pallas kernel implements;
    bit-exact oracle for ``kernels.threefry_mask_add``.

    Word ``i`` evaluates its own block and selects lane ``i & 1``; the
    kernel evaluates each block once, for both its words. Interleaving
    the two lanes instead would need a ``[n/2, 2]`` intermediate, which
    the TPU tiles to 128 lanes (64x its size); this form keeps every
    array 1-D.
    """
    if isinstance(counter_base, (int, np.integer)):
        counter_base = np.uint32(int(counter_base) & 0xFFFFFFFF)
    with jax.named_scope(KEYSTREAM):
        base = jnp.asarray(counter_base, jnp.uint32)
        idx = jnp.arange(n, dtype=jnp.uint32)
        y0, y1 = threefry2x32(key, base + (idx >> 1), jnp.zeros_like(idx))
        return jnp.where((idx & 1).astype(jnp.bool_), y1, y0)


def derive_key(master: jax.Array, *tags: int) -> jax.Array:
    """Derive a subkey from a uint32[2] master key and integer tags.

    A small KDF built from the PRF itself: fold each tag in with one
    Threefry application. Used for per-round / per-chunk / per-purpose
    domain separation.
    """
    k = jnp.asarray(master, jnp.uint32)
    for tag in tags:
        t = jnp.asarray(tag, jnp.uint32)
        y0, y1 = threefry2x32(k, t, jnp.uint32(0x9E3779B9))
        k = jnp.stack([y0, y1])
    return k


def derive_pair_key(seed_i: jax.Array, i: int | jax.Array, j: int | jax.Array) -> jax.Array:
    """Pairwise key for chain neighbours (i -> j).

    In the deployed system the pair key comes from an out-of-band exchange
    (paper §5.8: symmetric-key pre-negotiation; in practice X25519 +
    HKDF during Round 0). For the device data plane we model it as a KDF
    of a common provisioning seed and the ordered pair (i, j) — both ends
    can derive it, nobody else learns it without the provisioning seed.
    """
    i = jnp.asarray(i, jnp.uint32)
    j = jnp.asarray(j, jnp.uint32)
    y0, y1 = threefry2x32(jnp.asarray(seed_i, jnp.uint32), i, j)
    return jnp.stack([y0, y1])


class RoundCounter:
    """Host-side monotone counter allocator.

    Guarantees keystream non-reuse across aggregation rounds: each round
    reserves ``nwords`` of counter space per purpose. Plain Python (host
    control-plane state, never traced).

    The Threefry counter words are uint32, so the usable space per key is
    exactly ``2**32`` words. ``reserve`` refuses — *before* mutating any
    state — any reservation whose range ``[base, base + nwords)`` would
    cross that boundary: a silent wrap would hand out counters already
    consumed in an earlier round, i.e. reuse one-time pads. After a
    refusal the allocator is still valid for smaller reservations, and
    the remedy is a Round-0 key rotation (fresh pair keys ⇒ fresh
    counter space).
    """

    #: usable counter words per (key, purpose): the full uint32 range.
    LIMIT = 2**32

    def __init__(self) -> None:
        self._next = 0

    @property
    def remaining(self) -> int:
        """Counter words still available before a key rotation is due."""
        return self.LIMIT - self._next

    def reserve(self, nwords: int) -> int:
        nwords = int(nwords)
        if nwords < 0:
            raise ValueError(f"nwords must be >= 0, got {nwords}")
        if nwords > self.remaining:
            raise OverflowError(
                f"counter space exhausted: {self._next} of 2**32 words used, "
                f"{nwords} requested; rotate pair keys (Round 0) before reuse"
            )
        base = self._next
        self._next += nwords
        return base
