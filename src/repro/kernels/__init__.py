"""Pallas TPU kernels for the SAFE masking hot spots.

threefry_mask_add — fused keystream + fixed-point encode + masked add
chain_combine     — fused SAFE non-initiator hop (decrypt+add+re-encrypt)
bon_mask          — fused BON pairwise masking (baseline hot spot)

Each kernel has a pure-jnp oracle in ``ref.py`` and a jit'd wrapper in
``ops.py`` (compiled on a TPU, interpreted on the CPU backend).
"""
from repro.kernels.ops import (bon_mask, chain_combine, chain_combine_batched,
                               mask_add)

__all__ = ["mask_add", "chain_combine", "chain_combine_batched", "bon_mask"]
