"""Fused SAFE chain hop: decrypt + add-local + re-encrypt (Pallas/TPU).

The complete non-initiator step (paper §5.1.2 step 2) in one HBM pass:

    out = cipher − PRF(k_in, ctr) + encode(x_local) + PRF(k_out, ctr)

Both pads are generated in-register (VPU) and never materialized; the
kernel reads ``cipher`` and ``x`` once and writes ``out`` once — 12 bytes
of HBM traffic per element instead of 28+ for the unfused sequence
(pad_in read+write, decrypt read+write, encode, pad_out read+write, add).
Each grid step has a fixed cost: at blocks of 64 rows it, not the VPU's
throughput, set the pace on a TPU v5e. So the grid's block is taken from
the row count (``block_rows_for``, 1,024 rows when there are that many)
and the body works it in 64-row sub-tiles (PERF.md §6).

The wrapper runs the kernel over a (rows, 128) view of its operands
(``lane_view``) and lets the output take the incoming cipher's buffer
(``input_output_aliases``). A hop jitted with the cipher donated, as a
chain hop consumes its incoming cipher, then runs the kernel alone when V
is a multiple of 128 words: no pad, slice or copy around it. A caller
that keeps its incoming cipher pays one copy of it.

``chain_combine_batched`` is the multi-session form meant for
``serve/agg_engine.py`` (which runs the jnp chain today): a leading
session dim S with *per-session* key and counter scalars delivered via
scalar prefetch — the grid walks
(session, block) and each session's keys are read from SMEM at
``program_id(0)``, so S tenants' hops stream through one kernel launch
with zero per-session dispatch overhead.
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
from repro.obs.trace import CHAIN_COMBINE, TILE_PAD, TILE_SLICE


def _chain_combine_kernel(scalars, cipher_ref, x_ref, o_ref, *,
                          scale_bits: int, block_rows: int):
    i = pl.program_id(0)

    def tile(r, n):
        off = jnp.uint32(i * block_rows + r)
        # scalars = [kin0, kin1, kout0, kout1, base]
        pad_in = pad_for_block(scalars[0], scalars[1], scalars[4], (n, LANE), off)
        pad_out = pad_for_block(scalars[2], scalars[3], scalars[4], (n, LANE), off)
        rows = pl.ds(r, n)
        o_ref[rows, :] = (cipher_ref[rows, :] - pad_in
                          + encode_block(x_ref[rows, :], scale_bits) + pad_out)

    for_each_subtile(block_rows, tile)


@functools.partial(jax.jit, static_argnames=("scale_bits", "block_rows", "interpret"))
def chain_combine(
    cipher: jax.Array,
    x: jax.Array,
    key_in: jax.Array,
    key_out: jax.Array,
    counter_base: jax.Array | int = 0,
    *,
    scale_bits: int = 16,
    block_rows: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """One fused chain hop. cipher: uint32[V], x: f32[V] -> uint32[V].
    ``block_rows`` None takes the grid's block from the rows.

    The output is written over ``cipher``'s (rows, 128) view: donate
    ``cipher`` to the enclosing jit and, for V a multiple of 128, the hop
    needs no buffer but its output. Without donation XLA copies ``cipher``
    once first, so the caller's stays intact.
    """
    V = cipher.shape[0]
    with jax.named_scope(TILE_PAD):
        c2, block_rows, nblocks = lane_view(cipher, block_rows)
        x2, _, _ = lane_view(x, block_rows)

    scalars = jnp.concatenate([
        jnp.asarray(key_in, jnp.uint32).reshape(2),
        jnp.asarray(key_out, jnp.uint32).reshape(2),
        as_u32_scalar(counter_base).reshape(1),
    ])

    kernel = pl.pallas_call(
        functools.partial(_chain_combine_kernel, scale_bits=scale_bits,
                          block_rows=block_rows),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nblocks,),
            in_specs=[
                pl.BlockSpec((block_rows, LANE), lambda i, s: (i, 0)),
                pl.BlockSpec((block_rows, LANE), lambda i, s: (i, 0)),
            ],
            out_specs=pl.BlockSpec((block_rows, LANE), lambda i, s: (i, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct(c2.shape, jnp.uint32),
        input_output_aliases={1: 0},  # operands count the scalars: c2 -> out
        interpret=interpret,
        name=CHAIN_COMBINE,
    )
    with jax.named_scope(CHAIN_COMBINE):
        out = kernel(scalars, c2, x2)
    with jax.named_scope(TILE_SLICE):
        return out.reshape(-1)[:V]


def _chain_combine_batched_kernel(scalars, cipher_ref, x_ref, o_ref, *,
                                  scale_bits: int, block_rows: int):
    s = pl.program_id(0)  # session
    i = pl.program_id(1)  # block within the session's vector
    # per-session scalars at s*5: [kin0, kin1, kout0, kout1, base]
    b = s * 5

    def tile(r, n):
        off = jnp.uint32(i * block_rows + r)
        pad_in = pad_for_block(scalars[b], scalars[b + 1], scalars[b + 4],
                               (n, LANE), off)
        pad_out = pad_for_block(scalars[b + 2], scalars[b + 3],
                                scalars[b + 4], (n, LANE), off)
        rows = pl.ds(r, n)
        o_ref[0, rows, :] = (cipher_ref[0, rows, :] - pad_in
                             + encode_block(x_ref[0, rows, :], scale_bits)
                             + pad_out)

    for_each_subtile(block_rows, tile)


@functools.partial(jax.jit, static_argnames=("scale_bits", "block_rows",
                                             "interpret"))
def chain_combine_batched(
    cipher: jax.Array,
    x: jax.Array,
    keys_in: jax.Array,
    keys_out: jax.Array,
    counter_bases: jax.Array,
    *,
    scale_bits: int = 16,
    block_rows: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """S fused chain hops, one per session, in one kernel launch.

    Per session s the arithmetic is exactly ``chain_combine`` under that
    session's keys/counter — bit-identical to S separate calls (asserted
    in tests/test_kernels.py).

    Args:
      cipher: uint32[S, V] incoming hop ciphertexts.
      x: f32[S, V] local vectors.
      keys_in / keys_out: uint32[S, 2] per-session edge keys.
      counter_bases: uint32[S] per-session counter bases.
      block_rows: the grid's block; None takes it from the rows of one
        session's (rows, 128) view.

    Returns:
      uint32[S, V] outgoing ciphertexts, in a new buffer.

    Each session's row is viewed as (rows, 128) like ``chain_combine``'s
    operands. On a TPU that view of an [S, V] array is a relayout, not a
    bitcast: its (8, 128) tiles span sessions, so both operands and the
    output are copied once. Unlike ``chain_combine`` the output does not
    alias ``cipher``: no caller donates it (the engine runs the jnp
    chain), and aliasing makes every caller that keeps its cipher pay a
    copy of all S rows.
    """
    S, V = cipher.shape
    c3, block_rows, nblocks = lane_view(cipher, block_rows)
    x3, _, _ = lane_view(x, block_rows)

    # flat SMEM table [S*5]: rows of (kin0, kin1, kout0, kout1, base)
    scalars = jnp.concatenate([
        jnp.asarray(keys_in, jnp.uint32).reshape(S, 2),
        jnp.asarray(keys_out, jnp.uint32).reshape(S, 2),
        jnp.asarray(counter_bases, jnp.uint32).reshape(S, 1),
    ], axis=1).reshape(-1)

    out = pl.pallas_call(
        functools.partial(_chain_combine_batched_kernel,
                          scale_bits=scale_bits, block_rows=block_rows),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(S, nblocks),
            in_specs=[
                pl.BlockSpec((1, block_rows, LANE), lambda s, i, ref: (s, i, 0)),
                pl.BlockSpec((1, block_rows, LANE), lambda s, i, ref: (s, i, 0)),
            ],
            out_specs=pl.BlockSpec((1, block_rows, LANE),
                                   lambda s, i, ref: (s, i, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct(c3.shape, jnp.uint32),
        interpret=interpret,
    )(scalars, c3, x3)
    return out.reshape(S, -1)[:, :V]
