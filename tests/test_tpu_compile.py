"""Ahead-of-time compiles for a described TPU v5e — no chip needed.

The TPU compiler is installed without a TPU: it compiles for a described
topology and refuses what the chip would refuse (a program that does not
fit its 16 GiB of HBM, a kernel Mosaic cannot lower). These tests compile
the masking kernels and the engine program at the size of one real
update (every parameter of internvl2-1b) and keep the device path inside
the chip's memory. Nothing runs, so nothing here is a time or a result.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library at a time, so a file that loaded it
while being imported would break collection under several test workers.
"""
import os
import re
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from helpers import REPO

sys.path.insert(0, REPO)
import chip_smoke  # noqa: E402
from repro.core.types import ChainConfig  # noqa: E402
from repro.kernels.bon_mask import bon_mask  # noqa: E402
from repro.kernels.chain_combine import (chain_combine,  # noqa: E402
                                         chain_combine_batched)
from repro.kernels.threefry_mask_add import mask_add  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.obs.trace import CHAIN_COMBINE, TILE_PAD, TILE_SLICE  # noqa: E402
from repro.serve import AggregationEngine  # noqa: E402

#: every parameter of internvl2-1b (chip_smoke.update_words() computes it)
V_FULL = 493_753_344
#: internvl2-1b's published update: V_FULL plus the q/k/v biases and the
#: mlp1 projector. A multiple of 128 words but not of 1024, so its
#: (rows, 128) view ends in a part of an (8, 128) tile
V_PUBLISHED = 498_263_808
HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def topo(no_persistent_cache):
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_kernel(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), "kernel was not lowered"
    return compiled


def test_full_update_is_internvl2_1b():
    assert chip_smoke.update_words() == V_FULL


@pytest.mark.parametrize("kernel", ["mask_add", "chain_combine"])
def test_hop_kernels_compile_at_full_update(one_chip, kernel):
    f32 = _spec((V_FULL,), jnp.float32, one_chip)
    u32 = _spec((V_FULL,), jnp.uint32, one_chip)
    key = _spec((2,), jnp.uint32, one_chip)
    ctr = _spec((), jnp.uint32, one_chip)
    if kernel == "mask_add":
        compiled = _compile_kernel(
            lambda x, k, c: mask_add(x, k, c, interpret=False), f32, key, ctr)
    else:
        compiled = _compile_kernel(
            lambda c, x, ki, ko, b: chain_combine(c, x, ki, ko, b,
                                                  interpret=False),
            u32, f32, key, key, ctr)
    assert chip_smoke.planned_bytes(compiled) < HBM_BYTES


def _full_size_copies(text: str, words: int) -> list[str]:
    """Each pad, slice or copy in the compiled HLO ``text`` that makes a
    buffer of at least ``words``, flat or as (rows, 128)."""
    found = []
    for line in text.splitlines():
        m = re.search(r"= [a-z0-9]+\[(\d+)(,128)?\]\S* (pad|slice|copy)\(",
                      line)
        if m and int(m[1]) * (128 if m[2] else 1) >= words:
            found.append(line.strip())
    return found


@pytest.mark.parametrize("words", [V_FULL, V_PUBLISHED])
def test_donated_hop_is_the_kernel_alone_at_full_update(one_chip, words):
    """At a full update (a multiple of 128 words, not of a 64-row block)
    a hop that donates its cipher, as a chain hop does, runs the Pallas
    call on views of its operands and writes over the cipher: nothing of
    update size is padded, sliced or copied, and it plans no temporary
    buffer."""
    key = _spec((2,), jnp.uint32, one_chip)
    compiled = jax.jit(
        lambda c, x, ki, ko, b: chain_combine(c, x, ki, ko, b,
                                              interpret=False),
        donate_argnums=0).lower(
            _spec((words,), jnp.uint32, one_chip),
            _spec((words,), jnp.float32, one_chip),
            key, key, _spec((), jnp.uint32, one_chip)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert not _full_size_copies(text, words)
    assert compiled.memory_analysis().temp_size_in_bytes < 2**20


@pytest.mark.parametrize("words", [V_FULL, V_PUBLISHED])
def test_mask_add_pads_nothing_at_full_update(one_chip, words):
    """The initiator's kernel reads the update through a view too; the
    only pad left is the key scalars' packing."""
    compiled = _compile_kernel(
        lambda x, k, c: mask_add(x, k, c, interpret=False),
        _spec((words,), jnp.float32, one_chip),
        _spec((2,), jnp.uint32, one_chip), _spec((), jnp.uint32, one_chip))
    assert not _full_size_copies(compiled.as_text(), words)
    assert compiled.memory_analysis().temp_size_in_bytes < 2**20


def test_hop_names_survive_the_tpu_compile(one_chip):
    """The names a device trace's readers look for: after the TPU
    compile, the hop's Pallas call carries its kernel's scope and the
    copies around it carry ``tile_pad`` and ``tile_slice``."""
    V = 3 * 8192 + 5
    key = _spec((2,), jnp.uint32, one_chip)
    text = _compile_kernel(
        lambda c, x, ki, ko, b: chain_combine(c, x, ki, ko, b,
                                              interpret=False),
        _spec((V,), jnp.uint32, one_chip), _spec((V,), jnp.float32, one_chip),
        key, key, _spec((), jnp.uint32, one_chip)).as_text()
    calls = [line for line in text.splitlines()
             if "custom-call(" in line and "tpu_custom_call" in line]
    assert calls and all(f"/{CHAIN_COMBINE}/" in line for line in calls)
    for scope in (TILE_PAD, TILE_SLICE):
        assert re.search(rf'op_name="[^"]*/{scope}/[^"]*"', text), scope


@pytest.mark.parametrize("kernel", ["chain_combine_batched", "bon_mask"])
def test_batched_and_bon_kernels_compile(one_chip, kernel):
    S, V, m = 8, 1 << 22, chip_smoke.PAPER_LEARNERS - 1
    if kernel == "chain_combine_batched":
        _compile_kernel(
            lambda c, x, ki, ko, b: chain_combine_batched(c, x, ki, ko, b,
                                                          interpret=False),
            _spec((S, V), jnp.uint32, one_chip),
            _spec((S, V), jnp.float32, one_chip),
            _spec((S, 2), jnp.uint32, one_chip),
            _spec((S, 2), jnp.uint32, one_chip),
            _spec((S,), jnp.uint32, one_chip))
    else:
        _compile_kernel(
            lambda x, k, s: bon_mask(x, k, s, 7, interpret=False),
            _spec((V,), jnp.float32, one_chip),
            _spec((m, 2), jnp.uint32, one_chip),
            _spec((m,), jnp.int32, one_chip))


def test_smoke_round_fits_one_chip(one_chip):
    """chip_smoke's n=36 round: each of its steps plans well inside HBM."""
    progs, _ = chip_smoke.round_programs(V_FULL, 0, False, sharding=one_chip)
    for p in progs:
        assert chip_smoke.planned_bytes(p) < chip_smoke.HBM_BUDGET * HBM_BYTES


def test_engine_program_fits_v5e_2x2(topo):
    """The engine's n=4 chain at the full update on a 2x2 mesh fits each
    chip. A keystream with a [V/2, 2] intermediate is padded to 128 lanes
    by the TPU's tiling and asks for ~126 GB here."""
    mesh = make_mesh((4,), ("data",), devices=topo.devices)
    engine = AggregationEngine(mesh, ChainConfig(num_learners=4, mode="safe"),
                               slots=1, payload_words=V_FULL)
    rep = NamedSharding(mesh, P())
    args = (
        _spec((1, 4, V_FULL), jnp.float32, NamedSharding(mesh, P(None, "data"))),
        _spec((1, 2), jnp.uint32, rep),
        _spec((1, 2), jnp.uint32, rep),
        _spec((1,), jnp.uint32, rep),
        _spec((1, 4), jnp.float32, rep),
        _spec((1, 4), jnp.float32, rep),
        _spec((1,), jnp.int32, rep),
    )
    compiled = engine._program.lower(*args).compile()
    m = compiled.memory_analysis()
    per_device = (m.argument_size_in_bytes + m.temp_size_in_bytes
                  + m.output_size_in_bytes)
    assert per_device < HBM_BYTES, m
    assert "collective-permute" in compiled.as_text()
