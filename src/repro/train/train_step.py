"""The SAFE-integrated distributed train step.

One jitted SPMD program per (arch × mesh), structured as (DESIGN.md §3):

  shard_map — manual over the learner axis 'data' (+ 'pod'), auto 'model'
  ├─ per-learner forward/backward (GSPMD tensor-parallel over 'model';
  │    giant MoEs use manual expert parallelism over 'data')
  ├─ SAFE chain secure aggregation of the flat gradient (the paper's
  │    Round 1 — ppermute ring, masked in Z/2^32Z)
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
from repro.models.transformer import Model
from repro.obs.trace import FWD_BWD, SAFE_CHAIN, ZERO1
from repro.optim.adamw import AdamW, FlatAdamW
from repro.train.flatten import (
    combine_trees,
    flat_to_tree,
    is_expert_path,
    partition_tree,
    tree_size,
    tree_to_flat,
)
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
    sec_size: int
    padded_size: int
    round_words: int      # counter words one step reserves
    jit_fn: Any = None    # raw jitted shard_map step (dry-run lowering)
    params_abs: Any = None  # abstract params (local-expert view if EP)
    leafwise: bool = False
    sec_opt_abs: Any = None  # abstract sec AdamState when leafwise


def make_train_step(
    model: Model,
    aggregator: SecureAggregator,
    mesh: Mesh,
    *,
    lr=3e-4,
    learner_axis: str = "data",
    pod_axis: Optional[str] = None,
    grad_clip: float = 1.0,
    weight_decay: float = 0.1,
    donate: bool = True,
    chain_model_sharded: bool = False,
    leafwise: Optional[bool] = None,
) -> TrainStepBundle:
    """chain_model_sharded: beyond-paper optimization — run 16 parallel
    chains, one per model-axis shard of the flat gradient (each model rank
    chains its slice; privacy per-slice identical, per-device chain memory
    and PRF work /16). False = paper-faithful single full-vector chain.

    leafwise: aggregate per parameter tensor instead of one flat vector
    (counters domain-separated per leaf). Each leaf keeps its Megatron
    sharding through the chain — no giant replicated flat temp — at the
    cost of the flat ZeRO-1 master (a tree AdamW with model-sharded state
    is used instead). Auto-enabled when the flat vector would exceed 8 GB
    f32 per device (the giant archs)."""
    cfg = model.cfg
    n = aggregator.cfg.num_learners
    use_ep = cfg.ep_axis is not None
    flat_opt = FlatAdamW(lr=lr, weight_decay=weight_decay)
    ep_opt = AdamW(lr=lr, weight_decay=weight_decay, grad_clip=None)
    sec_opt = AdamW(lr=lr, weight_decay=weight_decay, grad_clip=grad_clip)

    # ---- size the secure-aggregated partition from an abstract template ----
    params_abs = jax.eval_shape(model.init, jax.random.key(0))
    if use_ep:
        # the template sees the LOCAL expert shard (what each rank holds)
        def _localize(path, x):
            if is_expert_path(path):
                # experts stacked as [n_units, E, d, f] -> shard E over ranks
                shape = (x.shape[0], x.shape[1] // n) + x.shape[2:]
                return jax.ShapeDtypeStruct(shape, x.dtype)
            return x
        from repro.train.flatten import _path_str
        params_abs_local = jax.tree_util.tree_map_with_path(
            lambda p, x: _localize(_path_str(p), x), params_abs)
    else:
        params_abs_local = params_abs
    sec_abs, _ = partition_tree(params_abs_local, lambda p: not is_expert_path(p))
    sec_size = tree_size(sec_abs)
    shard_len = -(-sec_size // n)
    padded_size = shard_len * n
    # the chain's payload, plus the weight word of a weighted mean
    round_words = padded_size + (1 if aggregator.cfg.weighted else 0)
    if leafwise is None:
        leafwise = sec_size * 4 > 8e9
    # per-leaf counter offsets (static): disjoint keystream ranges
    leaf_sizes = [int(np.prod(np.shape(l))) for l in jax.tree.leaves(sec_abs)]
    leaf_offsets = list(np.cumsum([0] + leaf_sizes[:-1]).astype(np.int64))

    # Megatron-TP output anchors ('data' stripped: it is manual here)
    from repro.models.sharding import param_pspecs, sanitize_spec
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

    _all_specs = param_pspecs(cfg, params_abs)
    _all_specs = jax.tree.map(_strip_manual, _all_specs, params_abs)
    sec_model_specs, _ = partition_tree(_all_specs,
                                        lambda p: not is_expert_path(p))

    # ---- per-rank step (inside shard_map) -----------------------------------
    def per_rank_step(params, master_shard, fopt_m, fopt_v, fopt_step,
                      ep_opt_state, sec_opt_state, tokens, prefix, weights,
                      epoch, counter, alive):
        tokens = tokens.reshape(tokens.shape[1:])  # drop learner dim
        if prefix is not None:
            prefix = prefix.reshape(prefix.shape[1:])
        my_w = weights[jax.lax.axis_index(learner_axis)]

        def loss_fn(p):
            logits, aux = model.forward(p, tokens, prefix)
            return next_token_loss(logits, tokens, cfg.prefix_embeds) + aux

        with jax.named_scope(FWD_BWD):
            loss, grads = jax.value_and_grad(loss_fn)(params)

        sec_g, ep_g = partition_tree(grads, lambda p: not is_expert_path(p))
        sec_params_tpl, _ = partition_tree(params,
                                           lambda p: not is_expert_path(p))
        # §8 collusion mitigation: the initiator role moves on every step
        rotate = initiator_rotation(epoch, counter, round_words,
                                    n).astype(jnp.int32)

        from repro.optim.adamw import AdamState
        fstate = AdamState(fopt_step, fopt_m, fopt_v)
        if leafwise:
            # per-leaf chains: each tensor keeps its Megatron sharding;
            # keystream domains separated by leaf index
            leaves, treedef = jax.tree.flatten(sec_g)
            avg_leaves = []
            for idx, leaf in enumerate(leaves):
                v = leaf.reshape(-1).astype(jnp.float32)
                if chain_model_sharded:
                    v = jax.lax.with_sharding_constraint(v, P("model"))
                with jax.named_scope(SAFE_CHAIN):
                    a = aggregator.aggregate(v, counter, alive=alive,
                                             domain=idx + 1, rotate=rotate,
                                             epoch=epoch)
                avg_leaves.append(a.reshape(leaf.shape))
            avg_tree = jax.tree.unflatten(treedef, avg_leaves)
            new_sec, sec_opt_state = sec_opt.update(avg_tree, sec_opt_state,
                                                    sec_params_tpl)
            new_master = master_shard  # unused placeholder
            grad_norm = jnp.sqrt(sum(jnp.sum(jnp.square(a))
                                     for a in avg_leaves))
        else:
            flat_g = tree_to_flat(sec_g)
            flat_g = jnp.pad(flat_g, (0, padded_size - sec_size))
            if chain_model_sharded:
                # 16 parallel chains over the auto 'model' axis
                flat_g = jax.lax.with_sharding_constraint(flat_g, P("model"))

            # ---- the paper's technique: secure gradient aggregation ----
            with jax.named_scope(SAFE_CHAIN):
                avg = aggregator.aggregate(flat_g, counter, alive=alive,
                                           rotate=rotate, epoch=epoch)

            # ---- ZeRO-1 slice update (public post-aggregation) ----
            with jax.named_scope(ZERO1):
                rank = jax.lax.axis_index(learner_axis)
                gshard = jax.lax.dynamic_slice(avg, (rank * shard_len,),
                                               (shard_len,))
                new_master, fstate = flat_opt.update(gshard, fstate,
                                                     master_shard)
                new_flat = jax.lax.all_gather(new_master, learner_axis,
                                              tiled=True)
                if pod_axis is not None:
                    new_flat = jax.lax.pmean(new_flat, pod_axis)  # identical
                new_sec = flat_to_tree(new_flat[:sec_size], sec_params_tpl)
                grad_norm = jnp.sqrt(jnp.sum(jnp.square(avg[:sec_size])))
        # anchor the rebuilt params to the Megatron-TP layout — without
        # this the all-gathered tree comes out replicated per device
        new_sec = jax.tree.map(
            lambda x, s: jax.lax.with_sharding_constraint(x, s)
            if s is not None else x,
            new_sec, sec_model_specs,
            is_leaf=lambda x: x is None)

        if use_ep:
            # expert grads were already globally summed by the a2a
            # transpose; update locally (state is per-rank = sharded).
            _, ep_params = partition_tree(params,
                                          lambda p: not is_expert_path(p))
            new_ep, ep_opt_state = ep_opt.update(ep_g, ep_opt_state, ep_params)
            new_params = combine_trees(new_sec, new_ep)
        else:
            new_params = new_sec

        metrics = {
            "loss": jax.lax.pmean(loss, learner_axis),
            "grad_scale": grad_norm,
            "weight": my_w,
        }
        if pod_axis is not None:
            metrics["loss"] = jax.lax.pmean(metrics["loss"], pod_axis)
        return (new_params, new_master, fstate.m, fstate.v, fstate.step,
                ep_opt_state, sec_opt_state, metrics)

    # ---- shard_map wiring ---------------------------------------------------
    manual = {learner_axis} | ({pod_axis} if pod_axis else set())

    def param_in_spec(path, leaf):
        if use_ep and is_expert_path(path):
            return P(None, learner_axis)  # [n_units, E, ...] -> shard E
        return P()

    from repro.train.flatten import _path_str as _ps
    params_specs = jax.tree_util.tree_map_with_path(
        lambda p, x: param_in_spec(_ps(p), x), params_abs)
    _, ep_abs = partition_tree(params_abs_local, lambda p: not is_expert_path(p))
    ep_opt_specs = None
    if use_ep:
        ep_opt_abs = jax.eval_shape(ep_opt.init, ep_abs)
        ep_opt_specs = jax.tree.map(
            lambda _: P(), ep_opt_abs)
        # m/v mirror the expert sharding; step is replicated
        ep_opt_specs = type(ep_opt_abs)(
            step=P(),
            m=jax.tree_util.tree_map_with_path(
                lambda p, x: P(None, learner_axis), ep_opt_abs.m),
            v=jax.tree_util.tree_map_with_path(
                lambda p, x: P(None, learner_axis), ep_opt_abs.v),
        )

    sec_opt_specs = P()
    if leafwise:
        sec_opt_abs = jax.eval_shape(sec_opt.init, sec_abs)
        sec_opt_specs = jax.tree.map(lambda _: P(), sec_opt_abs)

    flat_spec = P(learner_axis)
    batch_spec = P((pod_axis, learner_axis) if pod_axis else learner_axis)

    in_specs = (
        params_specs,        # params
        flat_spec,           # master_shard [n*shard_len]
        flat_spec, flat_spec, P(),   # fopt m, v, step
        ep_opt_specs if use_ep else P(),  # ep opt state
        sec_opt_specs,       # sec opt state (leafwise) or dummy
        batch_spec,          # tokens [pods*n, B_l, S]
        batch_spec if cfg.prefix_embeds else P(),  # prefix embeds or dummy
        P(),                 # weights [n]
        P(),                 # key epoch
        P(),                 # counter
        P(),                 # alive [n]
    )
    out_specs = (
        params_specs, flat_spec, flat_spec, flat_spec, P(),
        ep_opt_specs if use_ep else P(),
        sec_opt_specs,
        P(),                 # metrics (replicated)
    )

    def train_step(params, master, fm, fv, fstep, ep_state, sec_state, tokens,
                   prefix, weights, epoch, counter, alive):
        if not cfg.prefix_embeds:
            prefix = None
        if not use_ep:
            ep_state = None
        if not leafwise:
            sec_state = None
        out = per_rank_step(params, master, fm, fv, fstep, ep_state,
                            sec_state, tokens, prefix, weights, epoch,
                            counter, alive)
        out = list(out)
        if not use_ep:
            out[5] = jnp.zeros(())
        if not leafwise:
            out[6] = jnp.zeros(())
        return tuple(out)

    shard_fn = jax.shard_map(
        train_step, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        axis_names=frozenset(manual), check_vma=False)

    # the optimizer states of absent partitions are shared placeholders
    donated = (0, 1, 2, 3) + ((5,) if use_ep else ()) + \
        ((6,) if leafwise else ())
    jit_fn = jax.jit(shard_fn, donate_argnums=donated if donate else ())

    # ---- state init, placed as the step takes it ------------------------------
    def _sharding(spec):
        return NamedSharding(mesh, spec)

    is_spec = lambda x: isinstance(x, P)  # noqa: E731
    state_shardings = {
        "params": jax.tree.map(_sharding, params_specs, is_leaf=is_spec),
        "master": _sharding(flat_spec),
        "fm": _sharding(flat_spec),
        "fv": _sharding(flat_spec),
        "fstep": _sharding(P()),
        "ep_opt": (jax.tree.map(_sharding, ep_opt_specs, is_leaf=is_spec)
                   if use_ep else None),
        "sec_opt": (jax.tree.map(_sharding, sec_opt_specs, is_leaf=is_spec)
                    if leafwise else None),
    }

    @functools.partial(jax.jit, out_shardings=state_shardings)
    def _init_arrays(params):
        sec_p, _ = partition_tree(params, lambda p: not is_expert_path(p))
        if leafwise:
            flat = jnp.zeros((n,), jnp.float32)  # 1 elem/rank placeholder
        else:
            flat = tree_to_flat(sec_p)
            flat = jnp.pad(flat, (0, padded_size - sec_size))
        ep_state = None
        if use_ep:
            _, ep_p = partition_tree(params, lambda p: not is_expert_path(p))
            ep_state = ep_opt.init(ep_p)
        return {
            "params": params,
            "master": flat,
            "fm": jnp.zeros_like(flat),
            "fv": jnp.zeros_like(flat),
            "fstep": jnp.zeros((), jnp.int32),
            "ep_opt": ep_state,
            "sec_opt": sec_opt.init(sec_p) if leafwise else None,
        }

    def init_state_fn(params):
        """The step's state, on the devices and in the layout the step
        takes and returns, so the first step compiles what every later
        step runs."""
        return {**_init_arrays(params), "step": 0}

    @functools.cache
    def constants():
        """Per-step inputs that never change (all-ones weights and alive
        bitmap, the absent prefix and optimizer states), placed once on
        the first step."""
        put = functools.partial(jax.device_put, device=_sharding(P()))
        return (put(np.ones((n,), np.float32)),
                put(np.zeros((1,), np.float32)),
                put(np.zeros((), np.float32)))

    def step_fn(state, tokens, reservation, prefix=None, weights=None,
                alive=None):
        epoch, counter = reservation
        all_ones, no_prefix, no_state = constants()
        ep_state = state["ep_opt"] if use_ep else no_state
        sec_state = state["sec_opt"] if leafwise else no_state
        with jax.set_mesh(mesh):
            (params, master, fm, fv, fstep, ep_state, sec_state,
             metrics) = jit_fn(
                state["params"], state["master"], state["fm"], state["fv"],
                state["fstep"], ep_state, sec_state, tokens,
                no_prefix if prefix is None else prefix,
                all_ones if weights is None else weights,
                np.uint32(epoch), np.uint32(counter),
                all_ones if alive is None else alive)
        new_state = {
            "params": params, "master": master, "fm": fm, "fv": fv,
            "fstep": fstep, "ep_opt": ep_state if use_ep else None,
            "sec_opt": sec_state if leafwise else None,
            "step": state["step"] + 1,
        }
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
        leafwise=leafwise,
        sec_opt_abs=sec_opt_abs if leafwise else None,
    )
