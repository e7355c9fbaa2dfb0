"""Cryptographic substrate for SAFE secure aggregation.

Pure-JAX reference implementations of:
  - Threefry-2x32 counter-mode PRF (the keystream generator used for
    hop "encryption" — one-time-pad masking over Z/2^32Z).
  - Fixed-point codec (f32 <-> i32) so masking is exact modular arithmetic.
  - Key schedule / pairwise key derivation for chain neighbours.

These are the oracles for the Pallas kernels in ``repro.kernels``.
"""
from repro.crypto.prf import (
    threefry2x32,
    keystream_pair_lanes,
    derive_pair_key,
    derive_key,
)
from repro.crypto.fixedpoint import (
    FixedPointCodec,
    DEFAULT_SCALE_BITS,
)

__all__ = [
    "threefry2x32",
    "keystream_pair_lanes",
    "derive_pair_key",
    "derive_key",
    "FixedPointCodec",
    "DEFAULT_SCALE_BITS",
]
