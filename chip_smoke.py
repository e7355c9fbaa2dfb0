#!/usr/bin/env python3
"""Smoke test of SAFE's device path on a TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # a 2x2 host (v5e)

One chip: one whole SAFE round at the paper's n=36 learners over an update
the size of internvl2-1b (every parameter), driven through the public
Pallas kernels in ``repro.kernels``: the initiator masks with ``mask_add``
plus its private mask R, 35 ``chain_combine`` hops move the cipher down
the chain, and the initiator strips the last hop pad and R with the jnp
keystream. The unmasked total must equal the ring sum of the learners'
encoded vectors on every word. Then the session-batched hop and the BON
mask are checked bit for bit against their jnp oracles (``kernels/ref.py``).

Four chips: what exists only across chips. A chain needs at least 3 ranks
and one mesh rank is one learner, so one chip cannot run it.
  (a) ``AggregationEngine`` with n=4 learners (one per chip) over the same
      update size, one round with all alive and one with learner 2 dead;
      each published mean must equal the survivors' exact ring sum, decoded.
  (b) ``make_train_step`` with the ``safe`` and the ``insec`` aggregator on
      internlm2-1.8b at full width cut to 4 layers, on a (4, 1) mesh; the
      SAFE loss must track INSEC.

It needs a TPU: with none it says so and exits non-zero. Every check
raises on failure. The last line of stdout is one JSON object naming the
device; it is printed only when every phase passed.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.crypto.fixedpoint import FixedPointCodec  # noqa: E402
from repro.crypto.prf import (derive_key, derive_pair_key,  # noqa: E402
                              keystream_pair_lanes)
from repro.kernels import (bon_mask, chain_combine,  # noqa: E402
                           chain_combine_batched, mask_add)
from repro.kernels.ref import (bon_mask_ref,  # noqa: E402
                               chain_combine_batched_ref)
from repro.models import Model  # noqa: E402
from repro.train.flatten import tree_size  # noqa: E402

#: the paper's headline chain length (SAFE §6.1: 36 nodes)
PAPER_LEARNERS = 36
#: the update whose size the round carries: every parameter of this model
UPDATE_ARCH = "internvl2-1b"
#: the train-step model: full width, cut to TRAIN_LAYERS layers
TRAIN_ARCH, TRAIN_LAYERS = "internlm2-1.8b", 4
#: |SAFE loss - INSEC loss| bound, as in tests/test_train.py
TRAIN_LOSS_TOL = 5e-3
#: the decoded mean may differ from the f32 clear-text mean by this much
MEAN_TOL = 2.0**-16
#: share of the device's memory the round's largest program may plan for
HBM_BUDGET = 0.9
#: counter base of the round's pads (any uint32 works)
COUNTER_BASE = 12345
_TAG_HOP, _TAG_R = 0x50, 0x52
_CODEC = FixedPointCodec()


def log(msg: str) -> None:
    print(msg, flush=True)


def update_words(arch: str = UPDATE_ARCH) -> int:
    """Parameter count of ``arch``, from its abstract parameter tree."""
    abstract = jax.eval_shape(Model(get_config(arch)).init, jax.random.key(0))
    return tree_size(abstract)


def learner_vector(seed: int, i, V: int) -> jax.Array:
    """Learner i's f32[V] update, drawn on the device from ``seed``."""
    key = jax.random.fold_in(jax.random.key(seed), i)
    return jax.random.uniform(key, (V,), jnp.float32, -1.0, 1.0)


def planned_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)


def round_programs(V: int, seed: int, interpret: bool, sharding=None):
    """The round's three jitted steps (initiate, hop, unmask), compiled
    ahead of time for V words — for ``sharding``'s device when given."""
    u32 = jax.ShapeDtypeStruct((V,), jnp.uint32, sharding=sharding)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=sharding)
    idx = jax.ShapeDtypeStruct((), jnp.int32, sharding=sharding)

    def initiate(k_out, k_r):
        x = learner_vector(seed, 0, V)
        cipher = (mask_add(x, k_out, COUNTER_BASE, interpret=interpret)
                  + keystream_pair_lanes(k_r, V, COUNTER_BASE))
        return cipher, _CODEC.encode(x)

    def hop(cipher, ring_ref, i, k_in, k_out):
        x = learner_vector(seed, i, V)
        cipher = chain_combine(cipher, x, k_in, k_out, COUNTER_BASE,
                               interpret=interpret)
        return cipher, ring_ref + _CODEC.encode(x)

    def unmask(cipher, k_in, k_r):
        return (cipher - keystream_pair_lanes(k_in, V, COUNTER_BASE)
                - keystream_pair_lanes(k_r, V, COUNTER_BASE))

    t0 = time.perf_counter()
    progs = (
        jax.jit(initiate).lower(key, key).compile(),
        jax.jit(hop, donate_argnums=(0, 1)).lower(u32, u32, idx, key,
                                                  key).compile(),
        jax.jit(unmask, donate_argnums=0).lower(u32, key, key).compile(),
    )
    return progs, time.perf_counter() - t0


def one_chip_phase(*, n: int, V: int, seed: int, interpret: bool,
                   batched_sessions: int, batched_words: int,
                   bon_keys: int, bon_words: int) -> dict:
    """One SAFE round of n learners through the kernels, then the
    batched-hop and BON kernels against their oracles. Raises on any
    mismatch; returns what it measured."""
    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    limit = stats.get("bytes_limit")
    (initiate, hop, unmask), compile_s = round_programs(V, seed, interpret)
    planned = max(planned_bytes(p) for p in (initiate, hop, unmask))
    V_full = V
    while limit and planned > HBM_BUDGET * limit:
        V //= 2
        log(f"round: cut V {V_full} -> {V}: the largest step plans "
            f"{planned} bytes of {limit}")
        (initiate, hop, unmask), compile_s = round_programs(V, seed, interpret)
        planned = max(planned_bytes(p) for p in (initiate, hop, unmask))

    master = jnp.asarray(np.array([seed & 0xFFFFFFFF, 0x5AFE], np.uint32))
    hop_seed = derive_key(master, _TAG_HOP)
    # edge i carries learner i's output to learner i+1 (mod n)
    edges = [derive_pair_key(hop_seed, i, (i + 1) % n) for i in range(n)]
    k_r = derive_key(master, _TAG_R, 0)  # the initiator's private mask key

    cipher, ring_ref = initiate(edges[0], k_r)
    hop_s = []
    for i in range(1, n):
        t0 = time.perf_counter()
        cipher, ring_ref = hop(cipher, ring_ref, jnp.int32(i), edges[i - 1],
                               edges[i])
        jax.block_until_ready(cipher)
        hop_s.append(time.perf_counter() - t0)
    total = unmask(cipher, edges[n - 1], k_r)
    del cipher
    mismatched = int(jnp.sum(total != ring_ref))
    del ring_ref
    if mismatched:
        raise AssertionError(f"ring total differs from sum(encode(x_i)) on "
                             f"{mismatched} of {V} words")

    add = jax.jit(lambda acc, i: acc + learner_vector(seed, i, V),
                  donate_argnums=0)
    clear = jnp.zeros((V,), jnp.float32)
    for i in range(n):
        clear = add(clear, jnp.int32(i))
    max_err = float(jax.jit(lambda t, c: jnp.max(jnp.abs(
        _CODEC.decode_mean(t, n) - c / n)))(total, clear))
    del total, clear
    if not max_err <= MEAN_TOL:
        raise AssertionError(f"decoded mean off by {max_err} > {MEAN_TOL}")

    oracles = kernel_oracle_check(seed=seed, interpret=interpret,
                                  sessions=batched_sessions,
                                  words=batched_words, bon_keys=bon_keys,
                                  bon_words=bon_words)
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    return {"n": n, "V": V, "V_full": V_full, "compile_s": compile_s,
            "hop_median_s": statistics.median(hop_s),
            "planned_bytes": planned, "bytes_limit": limit,
            "ring_mismatched_words": mismatched, "max_mean_err": max_err,
            "peak_bytes_in_use": peak, **oracles}


def kernel_oracle_check(*, seed: int, interpret: bool, sessions: int,
                        words: int, bon_keys: int, bon_words: int) -> dict:
    """chain_combine_batched and bon_mask vs kernels/ref.py, bit for bit."""
    k = jax.random.split(jax.random.key(seed ^ 0xB0B), 7)
    S, V = sessions, words
    cipher = jax.random.bits(k[0], (S, V), jnp.uint32)
    x = jax.random.uniform(k[1], (S, V), jnp.float32, -50.0, 50.0)
    kin = jax.random.bits(k[2], (S, 2), jnp.uint32)
    kout = jax.random.bits(k[3], (S, 2), jnp.uint32)
    bases = jax.random.bits(k[4], (S,), jnp.uint32)
    got = chain_combine_batched(cipher, x, kin, kout, bases,
                                interpret=interpret)
    want = jax.jit(chain_combine_batched_ref)(cipher, x, kin, kout, bases)
    batched_bad = int(jnp.sum(got != want))
    del cipher, x, got, want

    xb = jax.random.uniform(k[5], (bon_words,), jnp.float32, -50.0, 50.0)
    keys = jax.random.bits(k[6], (bon_keys, 2), jnp.uint32)
    signs = jnp.where(jnp.arange(bon_keys) % 2 == 0, 1, -1).astype(jnp.int32)
    got = bon_mask(xb, keys, signs, COUNTER_BASE, interpret=interpret)
    want = jax.jit(bon_mask_ref)(xb, keys, signs, COUNTER_BASE)
    bon_bad = int(jnp.sum(got != want))
    if batched_bad or bon_bad:
        raise AssertionError(f"kernels differ from kernels/ref.py: batched "
                             f"{batched_bad} words, bon {bon_bad} words")
    return {"batched_shape": [S, V], "batched_mismatched_words": batched_bad,
            "bon_keys": bon_keys, "bon_words": bon_words,
            "bon_mismatched_words": bon_bad}


def engine_phase(mesh, *, V: int, seed: int, dead: int = 2) -> dict:
    """Two engine rounds over the mesh's 'data' axis (one learner per
    device): all alive, then learner ``dead`` down. Each published mean
    must equal the survivors' exact ring sum, decoded."""
    from repro.core.types import ChainConfig
    from repro.serve import AggregationEngine

    n = mesh.shape["data"]
    engine = AggregationEngine(mesh, ChainConfig(num_learners=n, mode="safe"),
                               slots=1, payload_words=V)
    values = np.random.default_rng(seed).random((n, V), dtype=np.float32)
    values *= 2.0
    values -= 1.0
    alives = [np.ones(n, np.float32), np.ones(n, np.float32)]
    alives[1][dead] = 0.0
    sessions = [engine.submit(values, alive=a, provisioning_seed=seed + r,
                              learner_master=seed + 100 + r)
                for r, a in enumerate(alives)]
    t0 = time.perf_counter()
    engine.run_until_done()
    wall = time.perf_counter() - t0

    decode = jax.jit(_CODEC.decode_mean)
    for sess, alive in zip(sessions, alives):
        ring = np.zeros(V, np.uint32)
        for i in np.flatnonzero(alive):
            ring += np.round(values[i] * np.float32(_CODEC.scale)).astype(
                np.int32).view(np.uint32)
        want = np.asarray(decode(ring, jnp.float32(alive.sum())))
        got = sess.results[0]
        if not np.array_equal(got, want):
            bad = int(np.sum(got != want))
            raise AssertionError(
                f"engine round (alive={alive.tolist()}) differs from the "
                f"survivors' decoded ring sum on {bad} of {V} words")
    return {"n": n, "V": V, "rounds": len(sessions), "dead_in_round_2": dead,
            "wall_s_2_rounds_incl_compile": wall, "bit_exact": True}


def train_phase(mesh, cfg, *, steps: int, batch_per_learner: int,
                seq_len: int, lr: float, seed: int) -> dict:
    """SAFE vs INSEC train steps from one init on the same seeded batches;
    the SAFE loss must stay within TRAIN_LOSS_TOL of INSEC's."""
    from repro.core import make_aggregator
    from repro.data import make_federated_batches
    from repro.train.train_step import make_train_step

    n = mesh.shape["data"]
    model = Model(cfg)
    stream = make_federated_batches(cfg, n, batch_per_learner, seq_len,
                                    seed=seed)
    batches = [jnp.asarray(stream.global_batch(s)["tokens"])
               for s in range(steps)]
    losses, step_s = {}, {}
    for mode in ("safe", "insec"):
        agg = make_aggregator(mode, n, axis="data")
        bundle = make_train_step(model, agg, mesh, lr=lr)
        state = bundle.init_state_fn(model.init(jax.random.key(seed)))
        losses[mode], times = [], []
        for s in range(steps):
            t0 = time.perf_counter()
            state, m = bundle.step_fn(state, batches[s],
                                      agg.reserve_round(bundle.round_words))
            losses[mode].append(float(m["loss"]))
            times.append(time.perf_counter() - t0)
        step_s[mode] = times
        del state
    gap = max(abs(a - b) for a, b in zip(losses["safe"], losses["insec"]))
    if not (np.isfinite(losses["safe"]).all() and gap < TRAIN_LOSS_TOL):
        raise AssertionError(f"SAFE loss {losses['safe']} does not track "
                             f"INSEC {losses['insec']} (gap {gap})")
    return {"arch": cfg.arch_id, "layers": cfg.n_layers,
            "params": bundle.sec_size, "steps": steps, "lr": lr,
            "loss_safe": losses["safe"], "loss_insec": losses["insec"],
            "max_loss_gap": gap, "step_s_safe": step_s["safe"],
            "step_s_insec": step_s["insec"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {platform!r}); this "
              f"smoke test runs only on the chip", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, JAX found {len(devices)}", file=sys.stderr)
        return 2

    from repro.launch.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    V = update_words()
    log(f"update: {UPDATE_ARCH} parameter count V={V}")

    if args.chips == 1:
        r = one_chip_phase(n=PAPER_LEARNERS, V=V, seed=args.seed,
                           interpret=False, batched_sessions=8,
                           batched_words=1 << 22, bon_keys=PAPER_LEARNERS - 1,
                           bon_words=1 << 22)
        log("round: " + json.dumps(r))
    else:
        from repro.launch.mesh import make_mesh
        r = engine_phase(make_mesh((4,), ("data",)), V=V, seed=args.seed)
        log("engine: " + json.dumps(r))
        cfg = dataclasses.replace(get_config(TRAIN_ARCH),
                                  n_layers=TRAIN_LAYERS)
        r = train_phase(make_mesh((4, 1), ("data", "model")), cfg, steps=3,
                        batch_per_learner=2, seq_len=256, lr=1e-4,
                        seed=args.seed)
        log("train: " + json.dumps(r))

    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
