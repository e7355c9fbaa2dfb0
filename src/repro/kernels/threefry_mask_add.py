"""Fused Threefry keystream + fixed-point encode + masked add (Pallas/TPU).

The device hot spot of SAFE: every chain hop and the initiator step
stream a large parameter vector through "generate pad, encode, add".
Unfused, that is three HBM round trips (pad materialization, encode,
add); this kernel does one read of ``x`` and one write of the masked
ciphertext — the pad never touches HBM.

TPU adaptation notes (DESIGN.md §4):
  * masking is element-wise VPU work; fusion saves the pad's HBM round
    trips. Each grid step has a fixed cost, so the grid's block is large
    (``block_rows_for``: 1,024 rows when the update has them), and the
    body works it in sub-tiles of 64 rows (``for_each_subtile``), each
    the (32, 128) counter tile of a 64-row block (PERF.md §6);
  * blocks are (block_rows, 128): lane-dim 128 matches the VPU/VREG lane
    width, block_rows a multiple of 8 for f32 sublane packing;
  * the wrappers run the grid over a (rows, 128) view of the update
    (``lane_view``): V is padded only to whole rows of 128 words, so an
    update of a multiple of 128 words is viewed in place, with no copy
    (a flat 32-bit array and its (rows, 128) view share one tiled
    layout on the TPU);
  * one Threefry-2x32 block gives two words, and each block is
    evaluated once: a tile's top half takes the blocks on the even lanes
    of a (block_rows/2, 128) counter tile, its bottom half those on the
    odd lanes, and a lane roll (XLU, not VPU) moves each block's other
    word beside its pair (``pad_for_block``). The word->counter map is
    ``keystream_pair_lanes``'s, so the pads are bit-identical to it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_ROTATIONS = ((13, 15, 26, 6), (17, 29, 16, 24))
_PARITY = np.uint32(0x1BD11BDA)  # np, not jnp: a jnp scalar would be a
# captured constant inside the Pallas kernel body

LANE = 128  # VPU lane width


def as_u32_scalar(x):
    """uint32 scalar from python int (wrapping) or traced value."""
    if isinstance(x, (int, np.integer)):
        return jnp.asarray(np.uint32(int(x) & 0xFFFFFFFF))
    return jnp.asarray(x, jnp.uint32)


BLOCK_ROWS = 1024  # 1024×128 u32 = 512 KiB / block operand
SUB_ROWS = 64  # rows the body computes at once: a (32, 128) counter tile


def block_rows_for(rows: int) -> int:
    """The grid's block height for a (rows, LANE) view: the rows spread
    evenly over ``cdiv(rows, BLOCK_ROWS)`` blocks, each rounded up to a
    multiple of 8 up to ``SUB_ROWS`` rows and of ``SUB_ROWS`` above. So
    a large view takes ``BLOCK_ROWS``-row blocks, a smaller one the
    smallest block that covers it, and no last block is mostly empty."""
    per_block = pl.cdiv(rows, pl.cdiv(rows, BLOCK_ROWS))
    step = 8 if per_block <= SUB_ROWS else SUB_ROWS
    return max(step, pl.cdiv(per_block, step) * step)


def for_each_subtile(block_rows: int, tile) -> None:
    """Calls ``tile(r, n)`` for the block's sub-tiles, rows [r, r + n),
    through a ``fori_loop``: ``SUB_ROWS`` rows each, or the whole block
    once when it is no larger."""
    sub = min(SUB_ROWS, block_rows)
    assert block_rows % sub == 0, (
        f"a block of {block_rows} rows is no whole number of {sub}-row "
        f"sub-tiles")

    def step(j, carry):
        tile(pl.multiple_of(j * sub, sub), sub)
        return carry

    jax.lax.fori_loop(0, block_rows // sub, step, 0)


def lane_view(a: jax.Array, block_rows: int | None
              ) -> tuple[jax.Array, int, int]:
    """``a[..., V]`` as ``[..., rows, LANE]``, the grid's block height
    (``block_rows``, or ``block_rows_for(rows)`` when it is None) and its
    block count over those rows, ``cdiv(rows, block)``.

    V is padded only to whole rows, not to whole blocks: the grid's last
    block may run past ``rows``, where Pallas reads don't-care values
    and drops the writes. A word's keystream depends only on its flat
    index, so the words kept are the same. When V is a multiple of
    ``LANE`` the pad has zero width and the view of a flat ``a`` is a
    bitcast; otherwise the pad copies ``a`` and the caller's slice back
    to V words copies its output.
    """
    vpad = (-a.shape[-1]) % LANE
    a = jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, vpad)])
    view = a.reshape(*a.shape[:-1], -1, LANE)
    rows = view.shape[-2]
    block = block_rows_for(rows) if block_rows is None else block_rows
    return view, block, pl.cdiv(rows, block)


def _rotl32(x, d: int):
    return (x << d) | (x >> (32 - d))


def threefry2x32_block(k0, k1, x0, x1):
    """Threefry-2x32 (20 rounds) on uint32 blocks — VPU-only arithmetic."""
    ks0, ks1 = k0, k1
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
        # the round constant joins the key word first, on scalars: one
        # vector add, not two
        x1 = x1 + (ks[(i + 2) % 3] + jnp.uint32(i + 1))
    return x0, x1


def pad_for_block(k0, k1, base, block_shape, row_offset):
    """uint32 keystream for a (rows, LANE) tile starting at flat offset
    ``row_offset*LANE``, matching crypto.prf.keystream_pair_lanes:
    word i = lane (i & 1) of Threefry(key, base + i//2).

    A tile's rows*LANE/2 blocks fill one (rows/2, LANE) tile of counters:
    even lanes hold the top half's blocks, odd lanes the bottom half's,
    each evaluated once. A top pair (2j, 2j+1) takes lane 0 of the block
    at lane 2j and lane 1 of the same block, rolled one lane right; a
    bottom pair takes lane 0 of the block at lane 2j+1, rolled one lane
    left, and lane 1 of it. The rolls run on the XLU, not the VPU."""
    rows, lanes = block_shape
    assert rows % 2 == 0, (
        f"pad_for_block pairs a tile's top and bottom halves: rows must "
        f"be even, got {rows}")
    half = (rows // 2, lanes)
    col = jax.lax.broadcasted_iota(jnp.uint32, half, 1)
    odd = col & jnp.uint32(1)
    row = (jax.lax.broadcasted_iota(jnp.uint32, half, 0) + row_offset
           + odd * jnp.uint32(rows // 2))
    ctr = base + ((row * jnp.uint32(lanes) + col) >> 1)
    y0, y1 = threefry2x32_block(k0, k1, ctr, jnp.zeros_like(ctr))
    odd = odd.astype(jnp.bool_)
    top = jnp.where(odd, pltpu.roll(y1, 1, 1), y0)
    bottom = jnp.where(odd, y1, pltpu.roll(y0, lanes - 1, 1))
    return jnp.concatenate([top, bottom], axis=0)


def encode_block(x, scale_bits: int):
    """f32 -> uint32 ring element (round-to-nearest-even), matching
    crypto.fixedpoint.FixedPointCodec.encode."""
    scaled = jnp.round(x.astype(jnp.float32) * jnp.float32(2.0**scale_bits))
    return scaled.astype(jnp.int32).view("uint32")


def _mask_add_kernel(scalars, x_ref, o_ref, *, scale_bits: int, block_rows: int):
    i = pl.program_id(0)

    def tile(r, n):
        pad = pad_for_block(scalars[0], scalars[1], scalars[2], (n, LANE),
                            jnp.uint32(i * block_rows + r))
        rows = pl.ds(r, n)
        o_ref[rows, :] = encode_block(x_ref[rows, :], scale_bits) + pad

    for_each_subtile(block_rows, tile)


@functools.partial(jax.jit, static_argnames=("scale_bits", "block_rows", "interpret"))
def mask_add(
    x: jax.Array,
    key: jax.Array,
    counter_base: jax.Array | int = 0,
    *,
    scale_bits: int = 16,
    block_rows: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """out[i] = encode(x[i]) + PRF(key, base + i)  (mod 2^32), fused.

    x: f32[V], any V: viewed as (rows, 128) in place when V is a multiple
    of 128, else padded to the next multiple (``lane_view``).
    key: uint32[2]. Returns uint32[V], a new buffer. ``block_rows`` None
    takes the grid's block from the rows (``block_rows_for``).
    """
    V = x.shape[0]
    x2, block_rows, nblocks = lane_view(x, block_rows)

    scalars = jnp.concatenate(
        [jnp.asarray(key, jnp.uint32).reshape(2),
         as_u32_scalar(counter_base).reshape(1)])

    out = pl.pallas_call(
        functools.partial(_mask_add_kernel, scale_bits=scale_bits,
                          block_rows=block_rows),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nblocks,),
            # index maps receive (grid_idx, scalar_ref) under scalar prefetch
            in_specs=[pl.BlockSpec((block_rows, LANE), lambda i, s: (i, 0))],
            out_specs=pl.BlockSpec((block_rows, LANE), lambda i, s: (i, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct(x2.shape, jnp.uint32),
        interpret=interpret,
    )(scalars, x2)
    return out.reshape(-1)[:V]
