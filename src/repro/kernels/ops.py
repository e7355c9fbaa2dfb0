"""Public jit'd entry points for the Pallas kernels.

``interpret=None`` (the default) compiles the kernel for the TPU on the
``"tpu"`` backend and runs the Pallas interpreter on the ``"cpu"``
backend (tests, rehearsals); any other backend raises rather than
silently interpreting. Pass ``interpret=`` explicitly to pin the choice.
The chain data plane (``core/chain.py``) does not call these yet: it
builds its pads with the jnp keystream (``crypto/prf.py``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.threefry_mask_add import mask_add as _mask_add
from repro.kernels.chain_combine import (
    chain_combine as _chain_combine,
    chain_combine_batched as _chain_combine_batched,
)
from repro.kernels.bon_mask import bon_mask as _bon_mask


def _default_interpret() -> bool:
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise RuntimeError(
            f"the Pallas kernels compile for a TPU or interpret on the CPU; "
            f"backend {backend!r} is neither")
    return backend == "cpu"


def _u32(counter_base):
    """Python ints wrap into uint32 before crossing the jit boundary."""
    if isinstance(counter_base, (int, np.integer)):
        return np.uint32(int(counter_base) & 0xFFFFFFFF)
    return counter_base


def mask_add(x, key, counter_base=0, *, scale_bits: int = 16,
             interpret: bool | None = None):
    """Fused encode+mask (SAFE initiator step / encrypt half of a hop)."""
    if interpret is None:
        interpret = _default_interpret()
    return _mask_add(x, key, _u32(counter_base), scale_bits=scale_bits,
                     interpret=interpret)


def chain_combine(cipher, x, key_in, key_out, counter_base=0, *,
                  scale_bits: int = 16, interpret: bool | None = None):
    """Fused SAFE non-initiator hop (decrypt + add + re-encrypt)."""
    if interpret is None:
        interpret = _default_interpret()
    return _chain_combine(cipher, x, key_in, key_out, _u32(counter_base),
                          scale_bits=scale_bits, interpret=interpret)


def chain_combine_batched(cipher, x, keys_in, keys_out, counter_bases, *,
                          scale_bits: int = 16,
                          interpret: bool | None = None):
    """Fused multi-session chain hop (one launch for S sessions' hops;
    per-session keys via scalar prefetch — serve/agg_engine substrate)."""
    if interpret is None:
        interpret = _default_interpret()
    return _chain_combine_batched(cipher, x, keys_in, keys_out,
                                  counter_bases, scale_bits=scale_bits,
                                  interpret=interpret)


def bon_mask(x, keys, signs, counter_base=0, *, scale_bits: int = 16,
             interpret: bool | None = None):
    """Fused BON pairwise masking (baseline hot spot)."""
    if interpret is None:
        interpret = _default_interpret()
    return _bon_mask(x, keys, signs, _u32(counter_base), scale_bits=scale_bits,
                     interpret=interpret)


__all__ = ["mask_add", "chain_combine", "chain_combine_batched", "bon_mask"]
