"""The names the program gives its device work: the chain hop's Pallas
kernel, its wrapper's tile copies and the jnp keystream appear in the
``op_name`` metadata of the compiled HLO, and naming them changed no
kernel's result.

Runs on the CPU with the kernels interpreted; the compiled HLO there keeps
the same metadata a TPU compile keeps.
"""
import re

import jax
import numpy as np
import pytest

from repro.crypto.prf import keystream_pair_lanes
from repro.kernels.bon_mask import bon_mask
from repro.kernels.chain_combine import chain_combine, chain_combine_batched
from repro.kernels.ref import (bon_mask_ref, chain_combine_batched_ref,
                               chain_combine_ref, mask_add_ref)
from repro.kernels.threefry_mask_add import mask_add
from repro.obs.trace import CHAIN_COMBINE, KEYSTREAM, TILE_PAD, TILE_SLICE

V = 3 * 8192 + 5  # not a whole number of (64, 128) tiles
S = 3             # sessions of the batched hop
M = 4             # BON pairwise keys
KERNELS = ("chain_combine", "chain_combine_batched", "mask_add", "bon_mask")


def _inputs(seed=0):
    rng = np.random.default_rng(seed)
    u32 = lambda *shape: rng.integers(0, 2**32, shape, dtype=np.uint32)  # noqa: E731
    f32 = lambda *shape: rng.uniform(-3, 3, shape).astype(np.float32)  # noqa: E731
    return {
        "mask_add": ((f32(V), u32(2), np.uint32(7)), mask_add,
                     mask_add_ref),
        "chain_combine": ((u32(V), f32(V), u32(2), u32(2), np.uint32(7)),
                          chain_combine, chain_combine_ref),
        "chain_combine_batched": ((u32(S, V), f32(S, V), u32(S, 2),
                                   u32(S, 2), u32(S)),
                                  chain_combine_batched,
                                  chain_combine_batched_ref),
        "bon_mask": ((f32(V), u32(M, 2),
                      np.array([1, -1, 1, -1], np.int32), np.uint32(7)),
                     bon_mask, bon_mask_ref),
    }


def op_names(fn, *args) -> set:
    """Every ``op_name`` path in the compiled HLO of ``fn(*args)``."""
    text = jax.jit(fn).lower(*args).compile().as_text()
    return set(re.findall(r'op_name="([^"]+)"', text))


def scopes_of(names) -> set:
    return {part for n in names for part in n.split("/")}


def test_the_names_are_plain_distinct_strings():
    names = [TILE_PAD, TILE_SLICE, KEYSTREAM, CHAIN_COMBINE]
    assert CHAIN_COMBINE == "chain_combine"
    assert len(set(names)) == len(names)
    assert all(re.fullmatch(r"[a-z_]+", n) for n in names)


@pytest.mark.parametrize("kernel", [CHAIN_COMBINE])
def test_kernel_wrapper_names_its_kernel_and_copies(kernel):
    args, fn, _ = _inputs()[kernel]
    names = op_names(lambda *a: fn(*a, interpret=True), *args)
    scopes = scopes_of(names)
    assert {TILE_PAD, TILE_SLICE, kernel} <= scopes
    # the interpreter pads the grid's ragged last block and slices it back
    # itself, inside the kernel's scope; each pad and slice the wrapper
    # makes is under its copy scope
    wrapper = [n for n in names if f"/{kernel}/" not in n]
    pads = [n for n in wrapper if re.search(r"/pad$", n)]
    slices = [n for n in wrapper if re.search(r"/slice$", n)]
    assert pads and all(f"/{TILE_PAD}/" in n for n in pads)
    assert slices and all(f"/{TILE_SLICE}/" in n for n in slices)
    # the kernel's body is traced under its own name alone
    body = [n for n in names if "/while/" in n]
    assert body and all(f"/{kernel}/" in n for n in body)
    assert not any(TILE_PAD in n or TILE_SLICE in n for n in body)


@pytest.mark.parametrize("words", [3 * 8192 + 5 * 1024, 3 * 8192 + 6 * 128,
                                   4 * 8192])
def test_aligned_update_has_no_tile_copies(words):
    """At V a multiple of 128 words the hop runs on a view of its
    operands: nothing is left under the copy scopes."""
    rng = np.random.default_rng(words)
    args = (rng.integers(0, 2**32, words, dtype=np.uint32),
            rng.uniform(-3, 3, words).astype(np.float32),
            np.array([1, 2], np.uint32), np.array([3, 4], np.uint32),
            np.uint32(7))
    names = op_names(lambda *a: chain_combine(*a, interpret=True), *args)
    assert CHAIN_COMBINE in scopes_of(names)
    assert not scopes_of(names) & {TILE_PAD, TILE_SLICE}


@pytest.mark.parametrize("kernel", KERNELS)
def test_named_kernels_match_the_reference_exactly(kernel):
    args, fn, ref = _inputs(seed=11)[kernel]
    got = np.asarray(fn(*args, interpret=True))
    want = np.asarray(ref(*args))
    assert got.shape == want.shape == args[0].shape
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("base", ["traced", "static"])
def test_keystream_is_named(base):
    key = np.array([3, 5], np.uint32)
    if base == "traced":
        names = op_names(lambda k, b: keystream_pair_lanes(k, V, b), key,
                         np.uint32(9))
    else:
        names = op_names(lambda k: keystream_pair_lanes(k, V, 9), key)
    threefry = [n for n in names if n.endswith(("/xor", "/shift_left"))]
    assert threefry and all(f"/{KEYSTREAM}/" in n for n in threefry)


def test_keystream_scope_reaches_a_fused_subtraction():
    """The unmask program's shape: the keystream fuses into a subtraction
    outside its scope, and the fused computation still carries it."""
    key = np.array([3, 5], np.uint32)

    def unmask(c, k, b):
        return c - keystream_pair_lanes(k, V, b)

    text = jax.jit(unmask).lower(np.zeros(V, np.uint32), key,
                                 np.uint32(9)).compile().as_text()
    fused = text[text.index("fused_computation"):]
    assert f"/{KEYSTREAM}/" in fused
