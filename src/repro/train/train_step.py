"""The SAFE-integrated distributed train step.

One jitted SPMD program per (arch × mesh), structured as (DESIGN.md §3):

  shard_map — manual over the learner axis 'data' (+ 'pod'), auto 'model'
  ├─ per-learner forward/backward (GSPMD tensor-parallel over 'model')
  ├─ SAFE chain secure aggregation of the whole gradient as one flat
  │    vector (the paper's Round 1 — ppermute ring, masked in Z/2^32Z)
  ├─ ZeRO-1 optimizer: each learner updates its 1/n slice of the f32
  │    master vector (safe: the aggregated gradient is public by
  │    protocol), then all-gathers the updated parameters
  └─ hierarchical federation over 'pod' (paper §5.10) via the
       aggregator's pod_axis

The same builder serves all four aggregator modes, so INSEC (plain
psum) vs SAFE is a one-flag ablation — that delta is the §Perf story.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.aggregators import SecureAggregator
from repro.models.sharding import param_pspecs, sanitize_spec
from repro.models.transformer import Model
from repro.obs.trace import FWD_BWD, SAFE_CHAIN, ZERO1
from repro.optim.adamw import AdamState, FlatAdamW
from repro.train.flatten import flat_to_tree, tree_size, tree_to_flat
from repro.train.loss import next_token_loss


def initiator_rotation(epoch, counter, round_words: int, n: int):
    """The initiator's offset for the step reserved at ``(epoch, counter)``
    (uint32, traced or not): the step's running index mod ``n``.

    Each key epoch opens at counter 0 and holds ``2**32 // round_words``
    steps, so the running index is ``epoch * steps_per_epoch + counter //
    round_words``; it is taken mod ``n`` factor by factor, so nothing
    overflows 32 bits. Consecutive steps differ by one, or by two where a
    run resumed mid-epoch at a base that is not a whole number of steps
    reaches the epoch's end: for ``n >= 3`` (every chain) the initiator
    moves on every step."""
    u32 = jnp.uint32
    per_epoch = (2**32 // round_words) % n
    k = jnp.asarray(counter, u32) // u32(min(round_words, 2**32 - 1))
    return ((jnp.asarray(epoch, u32) % u32(n)) * u32(per_epoch)
            + k % u32(n)) % u32(n)


@dataclasses.dataclass
class TrainStepBundle:
    """Everything the launcher needs: the jitted step + state builders.

    ``step_fn(state, tokens, reservation, prefix=None, weights=None,
    alive=None) -> (state, metrics)``; ``reservation`` is the
    ``(epoch, base)`` that ``aggregator.reserve_round(round_words)``
    returned for this step, and is consumed by it."""
    step_fn: Any
    init_state_fn: Any    # params -> state, placed as the step takes it
    state_shardings: Any  # NamedShardings of the state's arrays
    batch_spec: Any       # PartitionSpec for the token batch
    sec_size: int         # words of the gradient the chain aggregates
    padded_size: int
    round_words: int      # counter words one step reserves
    jit_fn: Any           # the jitted shard_map step, for ahead-of-time lowering
    params_abs: Any       # abstract params


def make_train_step(
    model: Model,
    aggregator: SecureAggregator,
    mesh: Mesh,
    *,
    lr=3e-4,
    learner_axis: str = "data",
    pod_axis: Optional[str] = None,
    weight_decay: float = 0.1,
) -> TrainStepBundle:
    """The SAFE train step of ``model`` over ``mesh``: every learner's
    whole gradient, flattened in tree order, goes through one chain of
    ``aggregator``; each learner then updates its ZeRO-1 slice of the
    master vector and all-gathers the new parameters."""
    cfg = model.cfg
    n = aggregator.cfg.num_learners
    flat_opt = FlatAdamW(lr=lr, weight_decay=weight_decay)

    # ---- size the chained vector from an abstract template ------------------
    params_abs = jax.eval_shape(model.init, jax.random.key(0))
    sec_size = tree_size(params_abs)
    shard_len = -(-sec_size // n)
    padded_size = shard_len * n
    # the chain's payload, plus the weight word of a weighted mean
    round_words = padded_size + (1 if aggregator.cfg.weighted else 0)

    # Megatron-TP output anchors ('data' stripped: it is manual here)
    axes_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))

    def _strip_manual(spec, leaf):
        parts = []
        for p in spec:
            if p == learner_axis or p == pod_axis or (
                    isinstance(p, tuple) and
                    (learner_axis in p or (pod_axis or "") in p)):
                parts.append(None)
            else:
                parts.append(p)
        return sanitize_spec(P(*parts), np.shape(leaf), axes_sizes)

    model_specs = jax.tree.map(_strip_manual, param_pspecs(cfg, params_abs),
                               params_abs)

    # ---- per-rank step (inside shard_map) -----------------------------------
    def per_rank_step(params, master_shard, fopt_m, fopt_v, fopt_step,
                      tokens, prefix, weights, epoch, counter, alive):
        tokens = tokens.reshape(tokens.shape[1:])  # drop learner dim
        if prefix is not None:
            prefix = prefix.reshape(prefix.shape[1:])
        my_w = weights[jax.lax.axis_index(learner_axis)]

        def loss_fn(p):
            logits, aux = model.forward(p, tokens, prefix)
            return next_token_loss(logits, tokens, cfg.prefix_embeds) + aux

        with jax.named_scope(FWD_BWD):
            loss, grads = jax.value_and_grad(loss_fn)(params)

        # §8 collusion mitigation: the initiator role moves on every step
        rotate = initiator_rotation(epoch, counter, round_words,
                                    n).astype(jnp.int32)

        flat_g = tree_to_flat(grads)
        flat_g = jnp.pad(flat_g, (0, padded_size - sec_size))

        # ---- the paper's technique: secure gradient aggregation ----
        with jax.named_scope(SAFE_CHAIN):
            avg = aggregator.aggregate(flat_g, counter, alive=alive,
                                       rotate=rotate, epoch=epoch)

        # ---- ZeRO-1 slice update (public post-aggregation) ----
        with jax.named_scope(ZERO1):
            rank = jax.lax.axis_index(learner_axis)
            gshard = jax.lax.dynamic_slice(avg, (rank * shard_len,),
                                           (shard_len,))
            new_master, fstate = flat_opt.update(
                gshard, AdamState(fopt_step, fopt_m, fopt_v), master_shard)
            new_flat = jax.lax.all_gather(new_master, learner_axis,
                                          tiled=True)
            if pod_axis is not None:
                new_flat = jax.lax.pmean(new_flat, pod_axis)  # identical
            new_params = flat_to_tree(new_flat[:sec_size], params)
            grad_norm = jnp.sqrt(jnp.sum(jnp.square(avg[:sec_size])))
        # anchor the rebuilt params to the Megatron-TP layout — without
        # this the all-gathered tree comes out replicated per device
        new_params = jax.tree.map(jax.lax.with_sharding_constraint,
                                  new_params, model_specs)

        metrics = {
            "loss": jax.lax.pmean(loss, learner_axis),
            "grad_scale": grad_norm,
            "weight": my_w,
        }
        if pod_axis is not None:
            metrics["loss"] = jax.lax.pmean(metrics["loss"], pod_axis)
        return (new_params, new_master, fstate.m, fstate.v, fstate.step,
                metrics)

    # ---- shard_map wiring ---------------------------------------------------
    manual = {learner_axis} | ({pod_axis} if pod_axis else set())
    flat_spec = P(learner_axis)
    batch_spec = P((pod_axis, learner_axis) if pod_axis else learner_axis)

    # Inputs and outputs 5 and 6 are placeholders that the step ignores:
    # ahead-of-time lowerings pass the step's arguments by position.
    in_specs = (
        P(),                 # params, whole on every learner
        flat_spec,           # master_shard [n*shard_len]
        flat_spec, flat_spec, P(),   # fopt m, v, step
        P(), P(),            # placeholders
        batch_spec,          # tokens [pods*n, B_l, S]
        batch_spec if cfg.prefix_embeds else P(),  # prefix embeds or dummy
        P(),                 # weights [n]
        P(),                 # key epoch
        P(),                 # counter
        P(),                 # alive [n]
    )
    out_specs = (
        P(), flat_spec, flat_spec, flat_spec, P(),
        P(), P(),            # placeholders
        P(),                 # metrics (replicated)
    )

    def train_step(params, master, fm, fv, fstep, _a, _b, tokens, prefix,
                   weights, epoch, counter, alive):
        if not cfg.prefix_embeds:
            prefix = None
        *state, metrics = per_rank_step(params, master, fm, fv, fstep,
                                        tokens, prefix, weights, epoch,
                                        counter, alive)
        return (*state, jnp.zeros(()), jnp.zeros(()), metrics)

    shard_fn = jax.shard_map(
        train_step, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        axis_names=frozenset(manual), check_vma=False)
    jit_fn = jax.jit(shard_fn, donate_argnums=(0, 1, 2, 3))

    # ---- state init, placed as the step takes it ------------------------------
    def _sharding(spec):
        return NamedSharding(mesh, spec)

    state_shardings = {
        "params": jax.tree.map(lambda _: _sharding(P()), params_abs),
        "master": _sharding(flat_spec),
        "fm": _sharding(flat_spec),
        "fv": _sharding(flat_spec),
        "fstep": _sharding(P()),
    }

    @functools.partial(jax.jit, out_shardings=state_shardings)
    def _init_arrays(params):
        flat = jnp.pad(tree_to_flat(params), (0, padded_size - sec_size))
        return {
            "params": params,
            "master": flat,
            "fm": jnp.zeros_like(flat),
            "fv": jnp.zeros_like(flat),
            "fstep": jnp.zeros((), jnp.int32),
        }

    def init_state_fn(params):
        """The step's state, on the devices and in the layout the step
        takes and returns, so the first step compiles what every later
        step runs."""
        return {**_init_arrays(params), "step": 0}

    @functools.cache
    def constants():
        """Per-step inputs that never change (all-ones weights and alive
        bitmap, the absent prefix, the placeholders), placed once on the
        first step."""
        put = functools.partial(jax.device_put, device=_sharding(P()))
        return (put(np.ones((n,), np.float32)),
                put(np.zeros((1,), np.float32)),
                put(np.zeros((), np.float32)))

    def step_fn(state, tokens, reservation, prefix=None, weights=None,
                alive=None):
        epoch, counter = reservation
        all_ones, no_prefix, placeholder = constants()
        with jax.set_mesh(mesh):
            params, master, fm, fv, fstep, _, _, metrics = jit_fn(
                state["params"], state["master"], state["fm"], state["fv"],
                state["fstep"], placeholder, placeholder, tokens,
                no_prefix if prefix is None else prefix,
                all_ones if weights is None else weights,
                np.uint32(epoch), np.uint32(counter),
                all_ones if alive is None else alive)
        new_state = {"params": params, "master": master, "fm": fm, "fv": fv,
                     "fstep": fstep, "step": state["step"] + 1}
        return new_state, jax.tree.map(np.asarray, metrics)

    return TrainStepBundle(
        step_fn=step_fn,
        init_state_fn=init_state_fn,
        state_shardings=state_shardings,
        batch_spec=batch_spec,
        sec_size=sec_size,
        padded_size=padded_size,
        round_words=round_words,
        jit_fn=jit_fn,
        params_abs=params_abs,
    )
