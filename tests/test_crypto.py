"""Crypto substrate: Threefry PRF + fixed-point codec properties."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.crypto.prf import (
    threefry2x32, keystream_pair_lanes, derive_key,
    derive_pair_key, RoundCounter)
from repro.crypto.fixedpoint import FixedPointCodec
from repro.crypto.np_impl import (
    threefry2x32_np, keystream_pair_lanes_np, derive_key_np,
    derive_pair_key_np, keystream_slice_np, NpFixedPoint)


class TestThreefry:
    def test_known_vector(self):
        # Threefry-2x32 (20 rounds) reference vector from the Random123
        # distribution: zero key, zero counter.
        y0, y1 = threefry2x32(jnp.zeros(2, jnp.uint32), jnp.uint32(0),
                              jnp.uint32(0))
        assert (int(y0), int(y1)) == (0x6B200159, 0x99BA4EFE)

    def test_matches_numpy_mirror(self):
        rng = np.random.RandomState(0)
        for _ in range(10):
            key = rng.randint(0, 2**32, 2, dtype=np.uint64).astype(np.uint32)
            x = rng.randint(0, 2**32, 64, dtype=np.uint64).astype(np.uint32)
            j0, j1 = threefry2x32(jnp.asarray(key), jnp.asarray(x),
                                  jnp.zeros_like(jnp.asarray(x)))
            n0, n1 = threefry2x32_np(key, x, np.zeros_like(x))
            np.testing.assert_array_equal(np.asarray(j0), n0)
            np.testing.assert_array_equal(np.asarray(j1), n1)

    @given(st.integers(0, 2**32 - 1), st.integers(0, 2**32 - 1),
           st.integers(1, 300), st.integers(0, 2**20))
    @settings(max_examples=25, deadline=None)
    def test_keystream_jnp_np_agree(self, k0, k1, n, base):
        key = np.array([k0, k1], np.uint32)
        np.testing.assert_array_equal(
            np.asarray(keystream_pair_lanes(jnp.asarray(key), n, base)),
            keystream_pair_lanes_np(key, n, base))

    @pytest.mark.parametrize("n", [0, 1, 2, 129, 8193])
    @pytest.mark.parametrize("base", [0, 2**31 + 1, 2**32 - 3, 2**33 + 7])
    def test_keystream_pair_lanes_interleaves_both_lanes(self, n, base):
        """Word 2b / 2b+1 = lane 0 / lane 1 of block base+b, including
        bases past 2**32 (wrapped) and counters that wrap mid-stream."""
        key = np.array([0x12345678, 0x9ABCDEF0], np.uint32)
        np.testing.assert_array_equal(
            np.asarray(keystream_pair_lanes(jnp.asarray(key), n, base)),
            keystream_pair_lanes_np(key, n, base & 0xFFFFFFFF))

    def test_keystream_disjoint_counters_differ(self):
        key = jnp.array([1, 2], jnp.uint32)
        a = np.asarray(keystream_pair_lanes(key, 128, 0))
        b = np.asarray(keystream_pair_lanes(key, 128, 64))
        assert not np.array_equal(a, b)

    def test_derive_key_domain_separation(self):
        m = jnp.array([7, 8], jnp.uint32)
        assert not np.array_equal(np.asarray(derive_key(m, 1)),
                                  np.asarray(derive_key(m, 2)))
        np.testing.assert_array_equal(np.asarray(derive_key(m, 3)),
                                      derive_key_np(np.array([7, 8], np.uint32), 3))

    def test_pair_key_symmetric_derivation(self):
        seed = jnp.array([3, 4], jnp.uint32)
        np.testing.assert_array_equal(
            np.asarray(derive_pair_key(seed, 2, 5)),
            derive_pair_key_np(np.array([3, 4], np.uint32), 2, 5))

    def test_round_counter_no_overlap(self):
        rc = RoundCounter()
        a = rc.reserve(1000)
        b = rc.reserve(500)
        assert b == a + 1000
        with pytest.raises(OverflowError):
            rc.reserve(2**32)

    def test_round_counter_overflow_guard(self):
        """The guard must fire *before* any counter in [base, base+n)
        wraps past 2**32 (a wrap would reuse one-time pads), must not
        poison the allocator, and must allow exactly the full space."""
        rc = RoundCounter()
        base = rc.reserve(2**32 - 4)  # nearly drain the space
        assert base == 0 and rc.remaining == 4
        with pytest.raises(OverflowError):
            rc.reserve(5)  # would wrap — refused pre-mutation
        assert rc.remaining == 4  # refusal left no partial reservation
        tail = rc.reserve(4)  # the exact remainder still fits
        assert tail == 2**32 - 4 and rc.remaining == 0
        with pytest.raises(OverflowError):
            rc.reserve(1)
        assert rc.reserve(0) == 2**32  # degenerate: no words, no wrap
        with pytest.raises(ValueError):
            rc.reserve(-1)

    def test_keystream_uniformity(self):
        """Coarse sanity: keystream bytes should look uniform (mean and
        bit balance), i.e. the pad actually masks."""
        ks = np.asarray(keystream_pair_lanes(jnp.array([9, 9], jnp.uint32),
                                             1 << 14))
        bits = np.unpackbits(ks.view(np.uint8))
        assert abs(bits.mean() - 0.5) < 0.01
        assert abs(ks.astype(np.float64).mean() / 2**32 - 0.5) < 0.02


class TestKeystreamSeekability:
    """The streaming chunk-combine rests on one property: slicing the
    keystream at an arbitrary word offset (``keystream_slice_np``)
    yields exactly the words of the single full-length stream — so a
    chunk-by-chunk decrypt/re-encrypt is bit-identical to the
    whole-vector one. counter_base is in two-word blocks, so odd
    offsets land mid-block; both parities must hold."""

    @given(st.integers(0, 2**32 - 1), st.integers(0, 2**32 - 1),
           st.integers(1, 257), st.integers(0, 2**20),
           st.lists(st.integers(0, 256), min_size=0, max_size=8))
    @settings(max_examples=50, deadline=None)
    def test_concatenated_slices_equal_full_stream(self, k0, k1, n, base,
                                                   cuts):
        key = np.array([k0, k1], np.uint32)
        full = keystream_pair_lanes_np(key, n, base)
        bounds = [0] + sorted(min(c, n) for c in cuts) + [n]
        parts = [keystream_slice_np(key, b - a, a, base)
                 for a, b in zip(bounds, bounds[1:])]
        np.testing.assert_array_equal(np.concatenate(parts), full)

    @pytest.mark.parametrize("chunk_words", [1, 2, 3, 16, 64])
    def test_chunk_edges(self, chunk_words):
        """The exact chunking the wire plane performs: V=103 over
        chunk_words-sized slices (ragged tail, odd offsets for odd
        chunk sizes) reassembles the full pad at every counter base."""
        key = np.array([0xDEAD, 0xBEEF], np.uint32)
        V = 103
        for base in (0, 1, 7, 2**31):
            full = keystream_pair_lanes_np(key, V, base)
            for k in range((V + chunk_words - 1) // chunk_words):
                start = k * chunk_words
                stop = min(start + chunk_words, V)
                np.testing.assert_array_equal(
                    keystream_slice_np(key, stop - start, start, base),
                    full[start:stop])

    def test_empty_slices(self):
        key = np.array([1, 2], np.uint32)
        assert keystream_slice_np(key, 0, 0, 0).size == 0
        assert keystream_slice_np(key, 0, 17, 5).size == 0
        # an empty slice between two non-empty ones changes nothing
        full = keystream_pair_lanes_np(key, 9, 3)
        parts = [keystream_slice_np(key, 4, 0, 3),
                 keystream_slice_np(key, 0, 4, 3),
                 keystream_slice_np(key, 5, 4, 3)]
        np.testing.assert_array_equal(np.concatenate(parts), full)

    def test_learner_crypto_pad_slice_matches_pad(self):
        """The machine-level wrapper: pad_slice == pad[start:stop] for
        the hop-pad keys the learners actually derive."""
        from repro.core.machines import LearnerCrypto

        crypto = LearnerCrypto(3, 0xC0FFEE, 0x5EED)
        V, counter = 103, 777
        full = crypto.pad(2, 3, V, counter)
        for start, stop in ((0, 16), (16, 33), (33, 103), (7, 8), (50, 50)):
            np.testing.assert_array_equal(
                crypto.pad_slice(2, 3, start, stop - start, counter),
                full[start:stop])


class TestFixedPoint:
    @given(st.lists(st.floats(-1000, 1000, allow_nan=False, width=32),
                    min_size=1, max_size=64),
           st.integers(8, 24))
    @settings(max_examples=50, deadline=None)
    def test_roundtrip(self, xs, bits):
        from hypothesis import assume
        codec = FixedPointCodec(bits)
        # codec contract: |x| must fit the ring headroom
        assume(max(abs(v) for v in xs) < codec.max_abs_value(1))
        x = jnp.asarray(np.asarray(xs, np.float32))
        dec = np.asarray(codec.decode(codec.encode(x)))
        np.testing.assert_allclose(dec, np.asarray(xs, np.float32),
                                   atol=1.0 / 2**bits + 1e-6)

    @given(st.integers(2, 64), st.integers(0, 2**31 - 1))
    @settings(max_examples=30, deadline=None)
    def test_sum_exactness_in_ring(self, n, seed):
        """Ring sums are exact: encode+add == add+encode to codec
        resolution — the property masking relies on."""
        rng = np.random.RandomState(seed % (2**31 - 1))
        codec = FixedPointCodec(16)
        xs = rng.uniform(-10, 10, (n, 17)).astype(np.float32)
        acc = jnp.zeros(17, jnp.uint32)
        for row in xs:
            acc = acc + codec.encode(jnp.asarray(row))
        dec = np.asarray(codec.decode(acc))
        np.testing.assert_allclose(dec, xs.sum(0), atol=n / 2**16 + 1e-4)

    def test_mask_cancels_exactly(self):
        """cipher - pad == plain, bit-exact (one-time-pad property)."""
        codec = FixedPointCodec(16)
        x = jnp.asarray(np.random.RandomState(0).uniform(-5, 5, 100)
                        .astype(np.float32))
        pad = keystream_pair_lanes(jnp.array([1, 2], jnp.uint32), 100)
        cipher = codec.encode(x) + pad
        np.testing.assert_array_equal(np.asarray(cipher - pad),
                                      np.asarray(codec.encode(x)))

    def test_np_mirror(self):
        codec = FixedPointCodec(16)
        ncodec = NpFixedPoint(16)
        x = np.random.RandomState(1).uniform(-100, 100, 256).astype(np.float32)
        np.testing.assert_array_equal(np.asarray(codec.encode(jnp.asarray(x))),
                                      ncodec.encode(x))
