"""Driver ``train_step``: a federation's SAFE train steps, back to back.

The program's own SAFE-aggregated train step (``repro.train.train_step``)
over a mesh of one learner per chip, built by the program's entry points
alone: ``make_aggregator("safe", n, scale_bits=...)``, ``make_train_step``
and ``launch.mesh.make_mesh`` over the cell's devices. Each step is one
SAFE round of the federation: every learner's gradient goes into the
chain (encode, pads, n-1 hops of a ``ppermute`` ring, unmask, decode), the
mean is broadcast, each learner updates its ZeRO-1 quarter of the AdamW
state and the parameters are all-gathered. Each step reserves fresh
counter space from the aggregator, which opens a new key epoch when the
space runs out; the step takes ``(epoch, counter)`` as inputs, so a
rotation compiles nothing. A step ends in ``block_until_ready`` on the new
parameters; ``round_s`` is the window over the steps it completed.

Set-up draws the weights on the devices from the seed, warms the step up
once, and then resumes the aggregator at a key epoch drawn from the seed,
one or two steps before that epoch's counter space runs out (as a run
resumed from a checkpoint would be), so the window crosses a rotation.

Correctness: after the window, a fresh state from the seed and fresh
counter space go through one step of the timed program at the timed
sizes; the plain reference (``refs/lm_step.py``) runs the same
parameters and batches, one learner per chip. Both are read on a seeded
probe of words drawn in each leaf of the parameter tree apart, and each
number is the worst leaf's:

- ``mean_grad_err``: the published mean gradient, read from the ZeRO-1
  first moment (with zero moments, m = (1 - b1) * mean after one step),
  against the reference's clear float32 mean of the learners' gradients;
- ``update_err``: each chip's parameters after the step against the
  reference's AdamW first step of that clear mean, as the norm of the
  difference over the norm of the reference's change (a state left
  unchanged reads 1). AdamW's first step moves a word by about lr *
  sign(g), so a word whose gradient is smaller than its bf16 roundoff
  turns its step around: the sound reading is far above rounding;
- ``adamw_err``: the same against AdamW's first step of the published
  mean, which leaves the gradient's roundoff out and holds the update, the
  all-gather and the return to the tree to float32 rounding.

The window must compile nothing and must cross a key epoch. The loss
against the reference's is printed, not checked: at random weights on
uniform tokens every loss lies near ln(vocab), whatever the forward did.
"""
from __future__ import annotations

import dataclasses
import json
import sys

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from bench.lib.cell import Check, Window, back_to_back, seed_key, seed_rng
from bench.refs import lm_step as ref

#: the program's AdamW (``optim.adamw.FlatAdamW``) past its learning rate,
#: as the configuration's ``assumed`` states it
ADAMW = {"b1": 0.9, "b2": 0.95, "eps": 1e-8, "weight_decay": 0.1}


def setup(ctx):
    return SafeTrainStep(ctx)


def model_config(cfg: dict):
    """The program's configuration of ``cfg["arch"]`` at the file's widths
    and depth."""
    from repro.configs import get_config
    return dataclasses.replace(
        get_config(cfg["arch"]), n_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], d_ff=cfg["intermediate_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["hidden_size"] // cfg["num_attention_heads"],
        vocab=cfg["vocab_size"], norm_eps=cfg["rms_norm_eps"],
        rope_theta=cfg["rope_theta"],
        tie_embeddings=cfg["tie_word_embeddings"])


class Probe:
    """A seeded probe of words, drawn in each leaf of the parameter tree
    apart: ``per_leaf`` words of a larger leaf, every word of a smaller
    one. ``idx[j]`` are leaf j's words (sorted), ``flat`` the same words
    in the program's flat vector (leaves end to end in tree order)."""

    def __init__(self, rng, params_abs, per_leaf: int):
        leaves = jax.tree_util.tree_flatten_with_path(params_abs)[0]
        self.names = [jax.tree_util.keystr(path) for path, _ in leaves]
        self.dtypes = [np.dtype(x.dtype) for _, x in leaves]
        sizes = [int(np.prod(x.shape)) for _, x in leaves]
        self.idx = [np.sort(rng.choice(size, min(size, per_leaf),
                                       replace=False)).astype(np.int32)
                    for size in sizes]
        ends = np.cumsum([len(i) for i in self.idx])
        self.slices = [slice(e - len(i), e) for e, i in zip(ends, self.idx)]
        offsets = np.cumsum([0] + sizes[:-1])
        self.flat = np.concatenate([o + i.astype(np.int64) for o, i in
                                    zip(offsets, self.idx)]).astype(np.int32)

    def worst(self, err) -> tuple[float, dict]:
        """``err(j, slice)`` of every leaf: the largest, and all by name."""
        each = {name: err(j, sl) for j, (name, sl)
                in enumerate(zip(self.names, self.slices))}
        return max(each.values()), each


class SafeTrainStep:
    def __init__(self, ctx):
        # a program without the train step's scopes has no key epochs
        # either: refuse before anything compiles
        from repro.obs.trace import FWD_BWD  # noqa: F401
        from repro.core import make_aggregator
        from repro.core.aggregators import KEY_EPOCHS
        from repro.launch.compile_cache import COMPILES, watch_compiles
        from repro.launch.mesh import make_mesh
        from repro.models import Model
        from repro.obs import MetricsRegistry
        from repro.train.train_step import make_train_step

        cfg, traffic = ctx.config, ctx.traffic
        n = cfg["learners"]
        if cfg["alive"] != n or cfg["weighted"]:
            raise ValueError("train_step runs all learners alive, unweighted")
        self.cfg, self.n, self.seed = cfg, n, ctx.seed
        self.rows, self.seq = cfg["rows_per_learner"], cfg["seq_len"]
        self.vocab = cfg["vocab_size"]
        self.per_leaf = traffic["probe_words_per_leaf"]
        self.limits = cfg["limits"]
        self.compiles = watch_compiles(MetricsRegistry())
        self._COMPILES, self._KEY_EPOCHS = COMPILES, KEY_EPOCHS

        self.model = Model(model_config(cfg))
        self.mesh = make_mesh((n, 1), ("data", "model"), devices=ctx.devices)
        self.agg = make_aggregator(cfg["aggregator"], n, axis="data",
                                   scale_bits=cfg["scale_bits"])
        self.bundle = make_train_step(self.model, self.agg, self.mesh,
                                      lr=cfg["lr"])
        if self.bundle.sec_size != cfg["update_words"]:
            raise ValueError(f"the step aggregates {self.bundle.sec_size} "
                             f"words, the file states {cfg['update_words']}")
        self._init = jax.jit(self.model.init,
                             out_shardings=NamedSharding(self.mesh, P()))
        self._batch_sharding = NamedSharding(self.mesh, self.bundle.batch_spec)
        self.rng = seed_rng(ctx.seed, 2)
        self.counts = {
            "tokens_per_step": n * self.rows * self.seq,
            "seq_len": self.seq, "layers": cfg["num_hidden_layers"],
            "hidden_size": cfg["hidden_size"],
            "intermediate_size": cfg["intermediate_size"],
            "num_attention_heads": cfg["num_attention_heads"],
            "num_key_value_heads": cfg["num_key_value_heads"],
            "vocab_size": self.vocab,
            "tie_word_embeddings": cfg["tie_word_embeddings"],
            "update_words": cfg["update_words"]}

        self.state = self.bundle.init_state_fn(self._init(seed_key(ctx.seed,
                                                                   0)))
        self._step(self.batch())  # warm-up: compiles the step
        info("memory_stats", ctx.devices[0].memory_stats())
        # resume one or two steps before the end of a key epoch
        rw = self.bundle.round_words
        left = int(self.rng.integers(1, 3))
        self.agg.resume(int(self.rng.integers(0, 2**31)),
                        2**32 - left * rw - int(self.rng.integers(1, rw)))
        self.window = {}

    def batch(self):
        """Every learner's rows of token ids, placed by learner."""
        toks = self.rng.integers(0, self.vocab, (self.n, self.rows, self.seq),
                                 dtype=np.int32)
        return jax.device_put(toks, self._batch_sharding)

    def _step(self, tokens):
        slot = self.agg.reserve_round(self.bundle.round_words)
        self.state, m = self.bundle.step_fn(self.state, tokens, slot)
        jax.block_until_ready(self.state["params"])
        return m

    def _counters(self) -> tuple[int, int]:
        return (self.compiles.counter(self._COMPILES).value,
                self.agg.metrics.counter(self._KEY_EPOCHS).value)

    def run_window(self, seconds: float) -> Window:
        before = self._counters()
        win = back_to_back(lambda i: self._step(self.batch()), seconds,
                           "round_s")
        after = self._counters()
        self.window = {"compiles": after[0] - before[0],
                       "rotations": after[1] - before[1]}
        return win

    def release(self) -> None:
        """The timed state leaves the devices; the reference needs them."""
        self.state = None

    def check(self) -> list[Check]:
        cfg = self.cfg
        key = seed_key(self.seed, 1)
        tokens, probe = draw(self.seed, cfg, self.per_leaf,
                             self.bundle.params_abs)
        on_chips = per_chip_probe(self.mesh)

        # one step of the timed program, fresh state, fresh counter space;
        # the first parameters are the master's first value (float32)
        params = self._init(key)
        before = np.asarray(on_chips(params, probe.idx))[0]
        self.state = self.bundle.init_state_fn(params)
        del params
        m = self._step(jax.device_put(tokens, self._batch_sharding))
        loss = float(m["loss"])
        mean = np.asarray(_take(self.state["fm"], probe.flat)) \
            / np.float32(1 - ADAMW["b1"])
        after = np.asarray(on_chips(self.state["params"], probe.idx))
        self.state = None

        losses, grads = reference(self.mesh, cfg)(
            self._init(key), jax.device_put(tokens, self._batch_sharding),
            probe.idx)
        want = ref.clear_mean(list(np.asarray(grads)))
        loss_ref = float(np.mean(np.asarray(losses)))

        grad_worst, grad_each = probe.worst(
            lambda j, sl: rel_rms(mean[sl], want[sl]))
        upd_worst, upd_each = probe.worst(
            updated_by(probe, before, after, want, cfg["lr"]))
        adamw_worst, adamw_each = probe.worst(
            updated_by(probe, before, after, mean, cfg["lr"]))
        info("per_leaf", {"mean_grad_err": grad_each, "update_err": upd_each,
                          "adamw_err": adamw_each})
        info("loss_rel_err", abs(loss - loss_ref) / loss_ref)
        lim = self.limits
        return [
            Check("mean_grad_err", grad_worst, lim["mean_grad_err"]),
            Check("update_err", upd_worst, lim["update_err"]),
            Check("adamw_err", adamw_worst, lim["adamw_err"]),
            Check("window_compiles", self.window["compiles"],
                  lim["window_compiles"]),
            Check("window_without_rotation",
                  int(self.window["rotations"] < 1),
                  lim["window_without_rotation"]),
        ]


#: words of a flat vector, wherever its shards live
_take = jax.jit(lambda a, i: a[i])


def info(name: str, value) -> None:
    print(f"info {name} = {json.dumps(value)}", file=sys.stderr, flush=True)


def rel_rms(got: np.ndarray, want: np.ndarray) -> float:
    """RMS of (got - want) over the RMS of want."""
    d, w = (got - want).astype(np.float64), want.astype(np.float64)
    num, den = np.sqrt(np.mean(d * d)), np.sqrt(np.mean(w * w))
    return float(num / den) if den > 0 else (0.0 if num == 0 else np.inf)


def rel_change(got: np.ndarray, want: np.ndarray,
               before: np.ndarray) -> float:
    """|got - want| over |want - before|: 0 when the step moved the words
    as it should, 1 when it left them where they were."""
    d = (got - want).astype(np.float64)
    c = (want - before).astype(np.float64)
    num, den = np.linalg.norm(d), np.linalg.norm(c)
    return float(num / den) if den > 0 else (0.0 if num == 0 else np.inf)


def updated_by(probe: Probe, before: np.ndarray, after: np.ndarray,
               mean: np.ndarray, lr: float):
    """``err(j, slice)``: how far each chip's parameters ``after`` [chips,
    words] lie from AdamW's first step of ``mean`` from ``before``, rounded
    to leaf j's dtype, over the size of that step (``rel_change``); the
    worst chip's."""
    def err(j, sl):
        new = ref.adamw_first_step(before[sl], mean[sl], lr=lr, **ADAMW)
        new = new.astype(probe.dtypes[j]).astype(np.float32)
        return max(rel_change(a[sl], new, before[sl]) for a in after)
    return err


def draw(seed: int, cfg: dict, per_leaf: int, params_abs):
    """The check's token ids [learners, rows, seq] and probe, from the
    seed."""
    rng = seed_rng(seed, 3)
    tokens = rng.integers(0, cfg["vocab_size"], (cfg["learners"],
                          cfg["rows_per_learner"], cfg["seq_len"]),
                          dtype=np.int32)
    return tokens, Probe(rng, params_abs, per_leaf)


def per_chip_probe(mesh):
    """(tree, idx) -> [chips, words]: each chip's own copy of a replicated
    tree, read at the probe."""
    return jax.jit(jax.shard_map(
        lambda tree, idx: ref.probe(tree, idx)[None], mesh=mesh,
        in_specs=(P(), P()), out_specs=P("data"), check_vma=False))


def reference(mesh, cfg: dict):
    """(params, tokens [learners, R, S], idx) -> (losses [learners],
    gradient words [learners, words]): the reference, learner i on the
    mesh's chip i with that chip's copy of the parameters."""
    def one(params, tokens, idx):
        loss, grad = ref.loss_and_grad(params, tokens[0], cfg)
        return loss[None], ref.probe(grad, idx)[None]
    return jax.jit(jax.shard_map(
        one, mesh=mesh, in_specs=(P(), P("data"), P()),
        out_specs=(P("data"), P("data")), check_vma=False))


def control(ctx, seeds) -> list[dict]:
    """The reference put in the program's place one precision down: the
    mean of the learners' clear float32 gradients taken through a 16-bit
    fixed-point secure sum (the codec's default) instead of the stated 24
    bits. Returns, per seed, the numbers the check compares, with the
    check's probe: the control's mean and the parameters AdamW makes of it
    against the clear float32 mean and AdamW's step of that. Its AdamW is
    the reference's own, so ``adamw_err`` reads 0."""
    from repro.launch.mesh import make_mesh
    from repro.models import Model

    cfg, n = ctx.config, ctx.config["learners"]
    mesh = make_mesh((n, 1), ("data", "model"), devices=ctx.devices)
    model = Model(model_config(cfg))
    init = jax.jit(model.init, out_shardings=NamedSharding(mesh, P()))
    batch = NamedSharding(mesh, P("data"))
    params_abs = jax.eval_shape(model.init, jax.random.key(0))
    run_ref, on_chips = reference(mesh, cfg), per_chip_probe(mesh)
    out = []
    for seed in seeds:
        tokens, probe = draw(seed, cfg, ctx.traffic["probe_words_per_leaf"],
                             params_abs)
        params = init(seed_key(seed, 1))
        before = np.asarray(on_chips(params, probe.idx))[0]
        _, grads = run_ref(params, jax.device_put(tokens, batch), probe.idx)
        grads = list(np.asarray(grads))
        want = ref.clear_mean(grads)
        low = ref.fixed_point_mean(grads, 16)
        # the parameters the control's mean would give, on every chip
        after = np.stack([np.zeros_like(before)] * n)
        for j, sl in enumerate(probe.slices):
            after[:, sl] = ref.adamw_first_step(
                before[sl], low[sl], lr=cfg["lr"], **ADAMW).astype(
                probe.dtypes[j]).astype(np.float32)
        grad_worst, grad_each = probe.worst(
            lambda j, sl: rel_rms(low[sl], want[sl]))
        upd_worst, upd_each = probe.worst(
            updated_by(probe, before, after, want, cfg["lr"]))
        out.append({"seed": seed, "mean_grad_err": grad_worst,
                    "update_err": upd_worst, "adamw_err": 0.0,
                    "per_leaf": {"mean_grad_err": grad_each,
                                 "update_err": upd_each}})
    return out
