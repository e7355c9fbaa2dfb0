"""Driver ``chain_round``: one federation's SAFE rounds, back to back.

A round of n learners (SAFE §5.1-§5.2) through the program's public
kernel entry points: the initiator masks its update with the fused
``repro.kernels.mask_add`` and adds its private mask R from the jnp
keystream (``crypto.prf.keystream_pair_lanes``); n-1 hops of
``repro.kernels.chain_combine`` strip the incoming edge pad, add the
learner's update and put on the outgoing pad; the initiator strips the
last pad and R. One chip does every learner's device work in chain
order: in a deployment each hop runs on its own organisation's chip, with
the same serial dependence, so the sum of the hop times is the device
part of the round's critical path.

The learners' updates are drawn on the device during set-up and stay
resident: ``distinct_updates`` vectors, learner i taking vector i mod k.
Every round takes fresh edge keys and a fresh stretch of counter space.
Each round ends in ``block_until_ready``; ``round_s`` is the window over
the rounds it completed.

Correctness: one round of the window, drawn from the seed, keeps its
unmasked total and a slice of its last hop's cipher. After the window
the total must equal the reference ring sum of the encoded updates on
every word, the cipher slice must equal that sum plus R plus the last
edge pad, and the program's decoded mean must lie within the
configuration's limit of the clear-text f32 mean (``refs/secure_sum.py``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib.cell import Check, Window, back_to_back, seed_key, seed_rng
from bench.refs import secure_sum as ref

def setup(ctx):
    return ChainRound(ctx)


def draw_updates(seed: int, k: int, V: int, bound: float, device):
    """k resident f32[V] updates, uniform in [-bound, bound), in one jitted
    call on the device."""
    def draw(key):
        return tuple(jax.random.uniform(jax.random.fold_in(key, j), (V,),
                                        jnp.float32, -bound, bound)
                     for j in range(k))
    return jax.jit(draw, out_shardings=jax.sharding.SingleDeviceSharding(
        device))(seed_key(seed, 0))


def programs(n: int, V: int, bits: int, probe_words: int):
    """The round's jitted programs: initiate, hop, unmask, probe. A
    device trace names them ``jit_safe_initiate``, ``jit_safe_hop``, ...

    ``keys[j]`` is edge j's key (learner j -> j+1 mod n); ``keys[n]`` is
    the initiator's private key for R."""
    from repro.crypto.prf import keystream_pair_lanes
    from repro.kernels import chain_combine, mask_add

    def safe_initiate(x, keys, base):
        return (mask_add(x, keys[0], base, scale_bits=bits)
                + keystream_pair_lanes(keys[n], V, base))

    def safe_hop(cipher, x, keys, base, i):
        return chain_combine(cipher, x, keys[i - 1], keys[i], base,
                             scale_bits=bits)

    def safe_unmask(cipher, keys, base):
        return (cipher - keystream_pair_lanes(keys[n - 1], V, base)
                - keystream_pair_lanes(keys[n], V, base))

    def probe(cipher, off):
        return jax.lax.dynamic_slice(cipher, (off,), (probe_words,))

    return (jax.jit(safe_initiate), jax.jit(safe_hop, donate_argnums=0),
            jax.jit(safe_unmask, donate_argnums=0), jax.jit(probe))


class ChainRound:
    def __init__(self, ctx):
        from repro.crypto.fixedpoint import FixedPointCodec

        cfg, traffic = ctx.config, ctx.traffic
        n, V, bits = cfg["learners"], cfg["update_words"], cfg["scale_bits"]
        if cfg["alive"] != n or cfg["weighted"]:
            raise ValueError("chain_round runs all learners alive, unweighted")
        self.n, self.V, self.k = n, V, traffic["distinct_updates"]
        self.limits = cfg["limits"]
        self.device = ctx.devices[0]
        self.rng = seed_rng(ctx.seed, 1)
        self.base = int(self.rng.integers(0, 2**32))
        self.probe_words = min(traffic["probe_words"], V)
        self.codec = FixedPointCodec(bits)
        (self._initiate, self._hop, self._unmask,
         self._probe) = programs(n, V, bits, self.probe_words)

        self.updates = draw_updates(ctx.seed, self.k, V,
                                    traffic["update_bound"], self.device)
        self.hop_index = [jax.device_put(np.int32(i), self.device)
                          for i in range(n)]
        self.rounds = 0
        self.kept = None
        self.counts = {"update_words": V, "learners": n}
        self._round(keep=True)  # warm-up: compiles every program
        self.kept = None

    def _round(self, keep: bool) -> None:
        """One whole round; when ``keep``, hold its total and cipher slice
        for the check."""
        n, dev = self.n, self.device
        # every key serves this round alone, and the round's V / 2 Threefry
        # counters are distinct: no pad is ever used twice
        keys_np = self.rng.integers(0, 2**32, (n + 1, 2), dtype=np.uint32)
        base = (self.base + self.rounds * self.V) % 2**32
        off = int(self.rng.integers(0, self.V - self.probe_words + 1))
        self.rounds += 1
        if keep:
            self.kept = None
        keys = jax.device_put(keys_np, dev)
        base_d = jax.device_put(np.uint32(base), dev)
        cipher = self._initiate(self.updates[0], keys, base_d)
        for i in range(1, n):
            cipher = self._hop(cipher, self.updates[i % self.k], keys, base_d,
                               self.hop_index[i])
        probe = self._probe(cipher, jax.device_put(np.int32(off), dev)) \
            if keep else None
        total = self._unmask(cipher, keys, base_d)
        total.block_until_ready()
        if keep:
            self.kept = {"total": total, "probe": probe, "keys": keys_np,
                         "base": base, "off": off}

    def run_window(self, seconds: float) -> Window:
        # round i is kept with chance 1/(i+1), drawn from the seed: a
        # reservoir sample of one round of the window
        return back_to_back(
            lambda i: self._round(self.rng.random() * (i + 1) < 1.0),
            seconds, "round_s")

    def release(self) -> None:
        """Nothing but the kept round and the updates stays on the device."""

    def check(self) -> list[Check]:
        kept, n = self.kept, self.n
        want = ref.ring_total(self.updates, n)
        ring_bad = int(jax.jit(lambda a, b: jnp.sum(a != b))(kept["total"],
                                                             want))
        off, W, keys = kept["off"], self.probe_words, kept["keys"]
        cipher_want = (np.asarray(want[off:off + W])
                       + ref.pad(keys[n], kept["base"], off, W)
                       + ref.pad(keys[n - 1], kept["base"], off, W))
        cipher_bad = int(np.sum(np.asarray(kept["probe"]) != cipher_want))
        del want
        err = float(jax.jit(lambda t, m: jnp.max(jnp.abs(
            self.codec.decode_mean(t, n) - m)))(kept["total"],
                                                ref.mean(self.updates, n)))
        lim = self.limits
        return [Check("ring_mismatch_words", ring_bad,
                      lim["ring_mismatch_words"]),
                Check("cipher_mismatch_words", cipher_bad,
                      lim["cipher_mismatch_words"]),
                Check("mean_abs_err", err, lim["mean_abs_err"])]


def control(ctx, seeds) -> list[dict]:
    """The reference put in the program's place one precision down: each
    update rounded to bfloat16 before it is encoded (what storing the
    resident updates in bf16 would do). Returns, per seed, the numbers the
    check compares, read against the full-precision reference."""
    cfg, traffic = ctx.config, ctx.traffic
    n, V = cfg["learners"], cfg["update_words"]
    out = []
    for seed in seeds:
        ups = draw_updates(seed, traffic["distinct_updates"], V,
                           traffic["update_bound"], ctx.devices[0])
        low = [u.astype(jnp.bfloat16).astype(jnp.float32) for u in ups]
        got = ref.ring_total(low, n)
        want = ref.ring_total(ups, n)
        ring_bad = int(jnp.sum(got != want))
        del want, low
        mean = ref.mean(ups, n)
        dec = got.view(jnp.int32).astype(jnp.float32) / np.float32(
            2.0 ** cfg["scale_bits"]) / np.float32(n)
        err = float(jnp.max(jnp.abs(dec - mean)))
        out.append({"seed": seed, "ring_mismatch_words": ring_bad,
                    "mean_abs_err": err})
        del ups, got, mean, dec
    return out
