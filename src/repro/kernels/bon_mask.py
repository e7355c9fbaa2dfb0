"""Fused BON pairwise masking (Pallas/TPU) — the baseline's hot spot.

    out = encode(x) + Σ_j signs[j] · PRF(keys[j], ctr)   (mod 2^32)

One learner's Round-2 masking applies m = n−1 pairwise pads plus the
self-mask: unfused that is m full keystream materializations (8·m bytes
of HBM traffic per element); fused, the pads are accumulated in VMEM and
the traffic is the same 12 bytes/element as a single SAFE hop — but the
VPU work is m× larger, which is exactly the O(n) compute asymmetry the
paper exploits (§2: SAFE needs 2 pads/hop regardless of n). The kernel
makes the comparison fair: BON's wall-clock disadvantage on TPU is
*compute*, not an artifact of naive fusion.

keys/signs arrive via scalar prefetch (SMEM) — they are O(n) words.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.threefry_mask_add import (
    LANE,
    as_u32_scalar,
    encode_block,
    for_each_subtile,
    lane_view,
    pad_for_block,
)


def _bon_mask_kernel(scalars, x_ref, o_ref, *, scale_bits: int,
                     block_rows: int, num_keys: int):
    i = pl.program_id(0)
    base = scalars[3 * num_keys]

    def tile(r, n):
        off = jnp.uint32(i * block_rows + r)
        rows = pl.ds(r, n)
        acc = encode_block(x_ref[rows, :], scale_bits)
        for j in range(num_keys):  # static unroll: n is a trace-time constant
            pad = pad_for_block(scalars[3 * j], scalars[3 * j + 1], base,
                                (n, LANE), off)
            sign_pos = scalars[3 * j + 2] > 0
            acc = jnp.where(sign_pos, acc + pad, acc - pad)
        o_ref[rows, :] = acc

    for_each_subtile(block_rows, tile)


@functools.partial(jax.jit, static_argnames=("scale_bits", "block_rows", "interpret"))
def bon_mask(
    x: jax.Array,
    keys: jax.Array,
    signs: jax.Array,
    counter_base: jax.Array | int = 0,
    *,
    scale_bits: int = 16,
    block_rows: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """x: f32[V]; keys: uint32[m, 2]; signs: int32[m] (+1/−1) -> uint32[V].
    ``block_rows`` None takes the grid's block from the rows."""
    V = x.shape[0]
    m = keys.shape[0]
    x2, block_rows, nblocks = lane_view(x, block_rows)

    # scalar layout: [k0_j, k1_j, sign_j]*m + [base]; sign encoded 1/0
    packed = jnp.concatenate([
        jnp.concatenate([
            jnp.asarray(keys, jnp.uint32),
            (jnp.asarray(signs, jnp.int32) > 0).astype(jnp.uint32).reshape(-1, 1),
        ], axis=1).reshape(-1),
        as_u32_scalar(counter_base).reshape(1),
    ])

    out = pl.pallas_call(
        functools.partial(_bon_mask_kernel, scale_bits=scale_bits,
                          block_rows=block_rows, num_keys=m),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nblocks,),
            in_specs=[pl.BlockSpec((block_rows, LANE), lambda i, s: (i, 0))],
            out_specs=pl.BlockSpec((block_rows, LANE), lambda i, s: (i, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct(x2.shape, jnp.uint32),
        interpret=interpret,
    )(packed, x2)
    return out.reshape(-1)[:V]
