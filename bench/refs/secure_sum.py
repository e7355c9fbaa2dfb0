"""Plain reference of a SAFE round's arithmetic, independent of the program.

What a round must publish, from SAFE (arXiv:2108.05475) §5.1-§5.2 and the
configuration: learner i's update x_i is encoded as 16-bit fixed point in
Z/2^32Z, encode(x) = int32(round_half_even(x * 2^16)) taken mod 2^32; the
unmasked total is the ring sum of the encodings, and the published mean
is int32(total) / 2^16 / n. A hop pad is the Threefry-2x32 (20 rounds)
keystream (Salmon et al., SC'11) of the edge key, word w taking lane
w & 1 of the block at counter (base + w >> 1, 0).

Written from those definitions in jax.numpy and numpy; it imports nothing
of the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

_ROT = ((13, 15, 26, 6), (17, 29, 16, 24))
_PARITY = 0x1BD11BDA


def encode(x: jax.Array, scale_bits: int = 16) -> jax.Array:
    """f32 -> Z/2^32Z, round half to even."""
    return jnp.round(x.astype(jnp.float32) * np.float32(2.0 ** scale_bits)
                     ).astype(jnp.int32).view(jnp.uint32)


@jax.jit
def _add_encoded(acc, x):
    return acc + encode(x)


@jax.jit
def _add(acc, x):
    return acc + x


def ring_total(updates, n: int) -> jax.Array:
    """Σ_{i<n} encode(updates[i mod k]) mod 2^32, one learner at a time."""
    acc = jnp.zeros(updates[0].shape, jnp.uint32)
    for i in range(n):
        acc = _add_encoded(acc, updates[i % len(updates)])
    return acc


def mean(updates, n: int) -> jax.Array:
    """The clear-text f32 mean of the n learners' updates."""
    acc = jnp.zeros(updates[0].shape, jnp.float32)
    for i in range(n):
        acc = _add(acc, updates[i % len(updates)])
    return acc / np.float32(n)


def threefry2x32(key, x0: np.ndarray, x1: np.ndarray):
    """Threefry-2x32, 20 rounds, on numpy uint32 arrays."""
    k0, k1 = np.uint32(key[0]), np.uint32(key[1])
    ks = (k0, k1, np.uint32(k0 ^ k1 ^ np.uint32(_PARITY)))
    x0 = (x0 + ks[0]).astype(np.uint32)
    x1 = (x1 + ks[1]).astype(np.uint32)
    for i in range(5):
        for r in _ROT[i % 2]:
            x0 = x0 + x1
            x1 = (x1 << np.uint32(r)) | (x1 >> np.uint32(32 - r))
            x1 = x1 ^ x0
        x0 = x0 + ks[(i + 1) % 3]
        x1 = x1 + ks[(i + 2) % 3] + np.uint32(i + 1)
    return x0, x1


def pad(key, base: int, first_word: int, words: int) -> np.ndarray:
    """Words [first_word, first_word + words) of the hop pad."""
    w = np.arange(first_word, first_word + words, dtype=np.uint64)
    ctr = ((np.uint64(base) + (w >> np.uint64(1))) & np.uint64(0xFFFFFFFF)
           ).astype(np.uint32)
    with np.errstate(over="ignore"):
        y0, y1 = threefry2x32(key, ctr, np.zeros_like(ctr))
    return np.where((w & np.uint64(1)).astype(bool), y1, y0)
