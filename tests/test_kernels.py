"""Pallas kernels vs pure-jnp oracles — integer kernels, so exact equality.

Sweeps shapes (including non-tile-aligned), counter bases, block sizes,
and key material. Runs in interpret mode on CPU (the kernels' TPU path is
identical modulo the Mosaic lowering).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

import repro.kernels.threefry_mask_add as tma
from repro.crypto.prf import keystream_pair_lanes
from repro.kernels.ops import (bon_mask, chain_combine,
                               chain_combine_batched, mask_add)
from repro.kernels.ref import (bon_mask_ref, chain_combine_batched_ref,
                               chain_combine_ref, mask_add_ref)
from repro.kernels.threefry_mask_add import (BLOCK_ROWS, LANE, SUB_ROWS,
                                             block_rows_for, pad_for_block)
from repro.kernels.threefry_mask_add import mask_add as raw_mask_add

#: the kinds of V the wrappers lay out differently, each viewed in place
#: as (rows, 128) but the last: whole (8, 128) tiles whose grid ends in a
#: ragged block, rows that end in a part of a tile (as internvl2-1b's
#: published update of 498,263,808 words does), a whole number of
#: (64, 128) blocks, and a V padded to the next 128
RAGGED, PART_TILE, WHOLE, UNALIGNED = (3 * 8192 + 5 * 1024,
                                       3 * 8192 + 6 * 128, 4 * 8192,
                                       3 * 8192 + 5)
LAYOUTS = [RAGGED, PART_TILE, WHOLE, UNALIGNED]
SHAPES = [1, 5, 127, 128, 129, 1000, 8192, 100_001] + LAYOUTS


@pytest.mark.parametrize("V", SHAPES)
def test_mask_add_shapes(V):
    rng = np.random.RandomState(V)
    x = jnp.asarray(rng.uniform(-100, 100, V).astype(np.float32))
    key = jnp.asarray(rng.randint(0, 2**32, 2, dtype=np.uint64).astype(np.uint32))
    got = mask_add(x, key, 42)
    want = mask_add_ref(x, key, 42)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("V", [3, 256, 4097])
@pytest.mark.parametrize("base", [0, 1, 2**31, 2**32 - 5])
def test_mask_add_counter_bases(V, base):
    x = jnp.asarray(np.random.RandomState(7).uniform(-1, 1, V).astype(np.float32))
    key = jnp.array([11, 13], jnp.uint32)
    np.testing.assert_array_equal(
        np.asarray(mask_add(x, key, base)),
        np.asarray(mask_add_ref(x, key, base)))


@pytest.mark.parametrize("block_rows", [8, 16, 64, 1024])
def test_mask_add_block_shapes(block_rows):
    V = 3000
    x = jnp.asarray(np.random.RandomState(1).uniform(-10, 10, V).astype(np.float32))
    key = jnp.array([5, 6], jnp.uint32)
    got = raw_mask_add(x, key, 0, block_rows=block_rows, interpret=True)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(mask_add_ref(x, key, 0)))


def test_block_rows_for():
    """A large update takes the large block; a smaller one the smallest
    block that covers it, a multiple of 8, and of the sub-tile above it;
    one just over a block spreads its rows over two even blocks."""
    assert block_rows_for(3_857_448) == BLOCK_ROWS == 1024
    assert block_rows_for(3_892_686) == BLOCK_ROWS
    for rows in (8, 24, 100):
        multiple = 8 if rows <= SUB_ROWS else SUB_ROWS
        block = block_rows_for(rows)
        assert block % multiple == 0 and rows <= block < rows + multiple
    assert [block_rows_for(r) for r in (1, 8, 24, 100)] == [8, 8, 24, 128]
    for rows in (BLOCK_ROWS + 1, 2 * BLOCK_ROWS + 1, 3_857_448):
        block = block_rows_for(rows)
        nblocks = -(-rows // block)
        assert nblocks == -(-rows // BLOCK_ROWS)
        assert block <= BLOCK_ROWS and block % SUB_ROWS == 0
        assert nblocks * block - rows < nblocks * SUB_ROWS
    assert block_rows_for(BLOCK_ROWS + 1) == 576


#: rows over three default blocks of ``BLOCK_ROWS`` rows, ending in a
#: ragged block and a part row
V_LARGE_BLOCKS = (3 * BLOCK_ROWS - 40) * LANE + 5


@pytest.mark.parametrize("kernel", ["mask_add", "chain_combine",
                                    "chain_combine_batched", "bon_mask"])
def test_kernels_at_default_block_match_ref(kernel):
    """Each wrapper at its default block, which it takes from the rows,
    equals ``kernels/ref.py`` word for word: compiled on a TPU,
    interpreted on the CPU."""
    V, S = V_LARGE_BLOCKS, 2
    rng = np.random.RandomState(18)

    def u32(*shape):
        return jnp.asarray(rng.randint(0, 2**32, shape, dtype=np.uint64)
                           .astype(np.uint32))

    x = jnp.asarray(rng.uniform(-50, 50, (S, V)).astype(np.float32))
    cipher, kin, kout = u32(S, V), u32(S, 2), u32(S, 2)
    if kernel == "mask_add":
        got, want = mask_add(x[0], kin[0], 9), mask_add_ref(x[0], kin[0], 9)
    elif kernel == "chain_combine":
        args = (cipher[0], x[0], kin[0], kout[0], 2**32 - 7)
        got, want = chain_combine(*args), chain_combine_ref(*args)
    elif kernel == "chain_combine_batched":
        args = (cipher, x, kin, kout, u32(S))
        got = chain_combine_batched(*args)
        want = chain_combine_batched_ref(*args)
    else:
        signs = jnp.asarray([1, -1, 1], jnp.int32)
        keys = u32(3, 2)
        got = bon_mask(x[0], keys, signs, 5)
        want = bon_mask_ref(x[0], keys, signs, 5)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _pads(key, base, V, block_rows):
    """``pad_for_block`` alone over a grid of (block_rows, 128) tiles that
    cover V words, through a one-output kernel: compiled on a TPU,
    interpreted on the CPU. The kernel is built anew on every call."""
    rows = pl.cdiv(V, LANE)

    def kernel(s, o_ref):
        o_ref[...] = pad_for_block(s[0], s[1], s[2], o_ref.shape,
                                   jnp.uint32(pl.program_id(0) * block_rows))

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(pl.cdiv(rows, block_rows),),
            in_specs=[],
            out_specs=pl.BlockSpec((block_rows, LANE), lambda i, s: (i, 0))),
        out_shape=jax.ShapeDtypeStruct((rows, LANE), jnp.uint32),
        interpret=jax.default_backend() == "cpu",
    )(jnp.concatenate([key, jnp.asarray([base], jnp.uint32)]))
    return out.reshape(-1)[:V]


@pytest.mark.parametrize("block_rows", [16, 64])
@pytest.mark.parametrize("base", [0, 2**32 - 3])
def test_pad_for_block_is_keystream_pair_lanes(block_rows, base):
    """Word for word the jnp keystream, over several blocks that end in a
    ragged one and a part row; from base 2^32 - 3 the counter wraps
    inside the first tile."""
    V = (3 * 64 + 24) * LANE + 5
    key = jnp.array([0x9E3779B9, 0x7F4A7C15], jnp.uint32)
    np.testing.assert_array_equal(
        np.asarray(_pads(key, base, V, block_rows)),
        np.asarray(keystream_pair_lanes(key, V, base)))


def test_pad_for_block_evaluates_each_block_once(monkeypatch):
    """A (64, 128) tile's 4,096 words are 2,048 Threefry blocks: one pad
    evaluates them as one (32, 128) tile, never a block per word."""
    shapes = []
    block = tma.threefry2x32_block

    def recording(k0, k1, x0, x1):
        shapes.append(x0.shape)
        return block(k0, k1, x0, x1)

    monkeypatch.setattr(tma, "threefry2x32_block", recording)
    key = jnp.array([5, 6], jnp.uint32)
    got = _pads(key, 7, 64 * LANE, 64)
    assert shapes == [(32, LANE)]
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(keystream_pair_lanes(key, 64 * LANE, 7)))


@pytest.mark.parametrize("scale_bits", [8, 16, 24])
def test_mask_add_scale_bits(scale_bits):
    V = 500
    x = jnp.asarray(np.random.RandomState(2).uniform(-3, 3, V).astype(np.float32))
    key = jnp.array([1, 2], jnp.uint32)
    np.testing.assert_array_equal(
        np.asarray(mask_add(x, key, 0, scale_bits=scale_bits)),
        np.asarray(mask_add_ref(x, key, 0, scale_bits=scale_bits)))


@pytest.mark.parametrize("V", [7, 640, 9000] + LAYOUTS)
def test_chain_combine(V):
    rng = np.random.RandomState(V)
    cipher = jnp.asarray(rng.randint(0, 2**32, V, dtype=np.uint64).astype(np.uint32))
    x = jnp.asarray(rng.uniform(-50, 50, V).astype(np.float32))
    kin = jnp.array([11, 22], jnp.uint32)
    kout = jnp.array([33, 44], jnp.uint32)
    np.testing.assert_array_equal(
        np.asarray(chain_combine(cipher, x, kin, kout, 9)),
        np.asarray(chain_combine_ref(cipher, x, kin, kout, 9)))


@pytest.mark.parametrize("S,V", [(1, 128), (3, 1000), (8, 257)]
                         + [(2, V) for V in LAYOUTS])
def test_chain_combine_batched(S, V):
    """Session-batched kernel == oracle (exact, per-session keys/counters
    delivered via scalar prefetch)."""
    rng = np.random.RandomState(S * 1000 + V)
    cipher = jnp.asarray(rng.randint(0, 2**32, (S, V), dtype=np.uint64)
                         .astype(np.uint32))
    x = jnp.asarray(rng.uniform(-50, 50, (S, V)).astype(np.float32))
    kin = jnp.asarray(rng.randint(0, 2**32, (S, 2), dtype=np.uint64)
                      .astype(np.uint32))
    kout = jnp.asarray(rng.randint(0, 2**32, (S, 2), dtype=np.uint64)
                       .astype(np.uint32))
    bases = jnp.asarray(rng.randint(0, 2**32, (S,), dtype=np.uint64)
                        .astype(np.uint32))
    np.testing.assert_array_equal(
        np.asarray(chain_combine_batched(cipher, x, kin, kout, bases)),
        np.asarray(chain_combine_batched_ref(cipher, x, kin, kout, bases)))


def test_chain_combine_batched_matches_single_calls():
    """Row s of the batched kernel is bit-identical to a standalone
    chain_combine under session s's keys — the engine's independence
    invariant at the kernel level."""
    rng = np.random.RandomState(42)
    S, V = 4, 513
    cipher = jnp.asarray(rng.randint(0, 2**32, (S, V), dtype=np.uint64)
                         .astype(np.uint32))
    x = jnp.asarray(rng.uniform(-5, 5, (S, V)).astype(np.float32))
    kin = jnp.asarray(rng.randint(0, 2**32, (S, 2), dtype=np.uint64)
                      .astype(np.uint32))
    kout = jnp.asarray(rng.randint(0, 2**32, (S, 2), dtype=np.uint64)
                       .astype(np.uint32))
    bases = jnp.asarray(np.arange(S).astype(np.uint32) * 1000)
    batched = np.asarray(chain_combine_batched(cipher, x, kin, kout, bases))
    for s in range(S):
        np.testing.assert_array_equal(
            batched[s],
            np.asarray(chain_combine(cipher[s], x[s], kin[s], kout[s],
                                     bases[s])))


@pytest.mark.parametrize("V", LAYOUTS)
def test_chain_combine_donated_matches_kept(V):
    """The hop's output takes the incoming cipher's buffer: under a jit
    that donates the cipher it returns the same words as under one that
    keeps it, and the kept cipher is left as it was."""
    rng = np.random.RandomState(V)
    cipher_np = rng.randint(0, 2**32, V, dtype=np.uint64).astype(np.uint32)
    x = jnp.asarray(rng.uniform(-50, 50, V).astype(np.float32))
    kin = jnp.array([11, 22], jnp.uint32)
    kout = jnp.array([33, 44], jnp.uint32)

    def hop(c, x):
        return chain_combine(c, x, kin, kout, 9)

    cipher = jnp.asarray(cipher_np)
    kept = np.asarray(jax.jit(hop)(cipher, x))
    np.testing.assert_array_equal(np.asarray(cipher), cipher_np)
    donated = np.asarray(jax.jit(hop, donate_argnums=0)(
        jnp.asarray(cipher_np), x))
    np.testing.assert_array_equal(donated, kept)
    np.testing.assert_array_equal(
        kept, np.asarray(chain_combine_ref(cipher_np, x, kin, kout, 9)))


def test_chain_combine_roundtrip_semantics():
    """A full 4-hop kernel chain equals the sum of the inputs (masks and
    pads cancel) — the kernel-level version of the protocol test."""
    from repro.crypto.fixedpoint import FixedPointCodec
    from repro.crypto.prf import derive_pair_key, keystream_pair_lanes
    V, n = 1000, 4
    rng = np.random.RandomState(0)
    vals = [jnp.asarray(rng.uniform(-5, 5, V).astype(np.float32))
            for _ in range(n)]
    seed = jnp.array([9, 9], jnp.uint32)
    keys = [derive_pair_key(seed, i, (i + 1) % n) for i in range(n)]
    rkey = jnp.array([77, 88], jnp.uint32)
    R = keystream_pair_lanes(rkey, V, 0)
    cipher = mask_add(vals[0], keys[0], 0) + R  # initiator: enc + R
    for i in range(1, n):
        cipher = chain_combine(cipher, vals[i], keys[i - 1], keys[i], 0)
    codec = FixedPointCodec(16)
    total = codec.decode((cipher - keystream_pair_lanes(keys[n - 1], V, 0)) - R)
    np.testing.assert_allclose(np.asarray(total),
                               np.asarray(sum(vals)), atol=n / 2**16 + 1e-4)


@pytest.mark.parametrize("m,V", [pytest.param(m, 2000, id=str(m))
                                 for m in (1, 2, 8, 15)]
                         + [(3, V) for V in LAYOUTS])
def test_bon_mask(m, V):
    rng = np.random.RandomState(m)
    x = jnp.asarray(rng.uniform(-50, 50, V).astype(np.float32))
    keys = jnp.asarray(rng.randint(0, 2**32, (m, 2), dtype=np.uint64)
                       .astype(np.uint32))
    signs = jnp.asarray(rng.choice([-1, 1], m).astype(np.int32))
    np.testing.assert_array_equal(
        np.asarray(bon_mask(x, keys, signs, 5)),
        np.asarray(bon_mask_ref(x, keys, signs, 5)))


def test_bon_pairwise_cancellation():
    """Opposite-sign pads cancel: bon_mask(x,+k) + bon_mask(y,-k) ==
    encode(x)+encode(y)."""
    from repro.crypto.fixedpoint import FixedPointCodec
    V = 512
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.uniform(-5, 5, V).astype(np.float32))
    y = jnp.asarray(rng.uniform(-5, 5, V).astype(np.float32))
    k = jnp.array([[123, 456]], jnp.uint32)
    a = bon_mask(x, k, jnp.array([1], jnp.int32), 0)
    b = bon_mask(y, k, jnp.array([-1], jnp.int32), 0)
    codec = FixedPointCodec(16)
    np.testing.assert_array_equal(
        np.asarray(a + b), np.asarray(codec.encode(x) + codec.encode(y)))


@given(st.integers(1, 4096), st.integers(0, 2**32 - 1), st.integers(0, 2**16))
@settings(max_examples=15, deadline=None)
def test_mask_add_property(V, k0, base):
    x = jnp.asarray(np.random.RandomState(V % 100).uniform(-10, 10, V)
                    .astype(np.float32))
    key = jnp.array([k0, k0 ^ 0xDEADBEEF], jnp.uint32)
    np.testing.assert_array_equal(
        np.asarray(mask_add(x, key, base)),
        np.asarray(mask_add_ref(x, key, base)))
