"""FedAvg with SAFE-secure delta aggregation (the paper's use case).

Cross-organizational federated learning (§1): each learner runs ``k``
local optimizer steps on its private shard, then the *model delta*
Δ_l = θ_l − θ_round is securely aggregated — weighted by local sample
counts via the paper's §5.6 weighted-averaging feature, so no learner
reveals its dataset size — and applied to the shared model.

Two runtimes consume the same local update:

  * :func:`make_federated_round` — the whole round as one SPMD program
    (local steps are a lax.scan inside the shard_map region, deltas go
    through the device-plane chain of ``core/chain.py``);
  * :func:`make_wire_federated` — per-learner standalone jit of the
    *identical* :func:`make_local_update` body, producing the numpy
    callables :func:`repro.net.client.run_federated_round_net` (one
    round, session rebuilt per call) and
    :func:`repro.net.client.run_federated_rounds_net` (R rounds on one
    persistent broker session — key material, connections and counter
    space amortized across rounds, deltas chunk-streamed through the
    hop-level streaming combine) drive over a real broker
    (docs/PROTOCOL.md §6/§11).

Because both paths share one local-update function and both aggregation
planes share one fixed-point/PRF substrate, a wire round's published
delta is bit-identical to the in-SPMD round for the same seeds
(asserted in tests/test_train.py::test_wire_round_delta_bit_identical).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.aggregators import SecureAggregator
from repro.models.transformer import Model
from repro.optim.adamw import AdamW
from repro.train.flatten import flat_to_tree, tree_size, tree_to_flat
from repro.train.loss import next_token_loss


@dataclasses.dataclass
class FederatedBundle:
    """``round_fn(params, tokens, reservation, weights=None, alive=None)
    -> (params, metrics)``; ``reservation`` is the ``(epoch, base)`` that
    ``aggregator.reserve_round(round_words)`` returned for this round."""
    round_fn: Any
    init_state_fn: Any
    round_words: int  # counter words one round reserves


def make_local_update(
    model: Model,
    *,
    local_steps: int = 4,
    local_lr: float = 1e-3,
) -> Callable[[Any, jax.Array], tuple]:
    """One learner's FedAvg local update, free of collectives.

    Returns ``local_update(params, tokens) -> (delta_flat, mean_loss)``
    where ``tokens`` is int32[local_steps, B, S] (one microbatch per
    local optimizer step) and ``delta_flat`` is f32[P] in the canonical
    :mod:`repro.train.flatten` layout. The function contains no
    ``axis_index``/collective ops, so it composes *inside* a shard_map
    region (``make_federated_round``) and compiles standalone per
    learner (``make_wire_federated``) — the factoring that lets the wire
    plane's learners run real local steps.
    """
    cfg = model.cfg
    local_opt = AdamW(lr=local_lr, weight_decay=0.0, grad_clip=1.0)

    def local_update(params, tokens):
        opt_state = local_opt.init(params)

        def local_step(carry, batch):
            p, s = carry

            def loss_fn(q):
                logits, aux = model.forward(q, batch)
                return next_token_loss(logits, batch, cfg.prefix_embeds) + aux

            loss, grads = jax.value_and_grad(loss_fn)(p)
            p, s = local_opt.update(grads, s, p)
            return (p, s), loss

        (new_params, _), losses = jax.lax.scan(
            local_step, (params, opt_state), tokens)
        delta = tree_to_flat(new_params) - tree_to_flat(params)
        return delta, losses.mean()

    return local_update


def apply_delta(params: Any, avg_delta) -> Any:
    """Merge a published average delta back into the parameter tree —
    the single apply formula both runtimes share."""
    merged = tree_to_flat(params) + jnp.asarray(avg_delta, jnp.float32)
    return flat_to_tree(merged, params)


def make_federated_round(
    model: Model,
    aggregator: SecureAggregator,
    mesh: Mesh,
    *,
    local_steps: int = 4,
    local_lr: float = 1e-3,
    learner_axis: str = "data",
    pod_axis: Optional[str] = None,
    return_delta: bool = False,
) -> FederatedBundle:
    """Build one FedAvg round: k local AdamW steps then weighted SAFE
    aggregation of the deltas. Aggregator must have cfg.weighted=True to
    exercise §5.6 (falls back to plain mean otherwise).

    ``return_delta=True`` adds the published f32[P] ``avg_delta`` to the
    metrics dict — the cross-plane parity hook (tests compare it against
    the wire-trained round's published delta bit for bit).
    """
    n = aggregator.cfg.num_learners
    local_update = make_local_update(model, local_steps=local_steps,
                                     local_lr=local_lr)

    def per_rank_round(params, tokens, weights, epoch, counter, alive):
        # tokens: [1, local_steps, B_l, S] for this learner
        tokens = tokens.reshape(tokens.shape[1:])
        my_w = weights[jax.lax.axis_index(learner_axis)]

        delta, loss_mean = local_update(params, tokens)
        # §5.6: weighted secure mean of deltas; weights stay private
        avg_delta = aggregator.aggregate(delta, counter, alive=alive,
                                         weights=my_w, epoch=epoch)
        out_params = apply_delta(params, avg_delta)
        metrics = {
            "local_loss": jax.lax.pmean(loss_mean, learner_axis),
            "delta_norm": jnp.sqrt(jnp.sum(jnp.square(avg_delta))),
        }
        if return_delta:
            metrics["avg_delta"] = avg_delta
        return out_params, metrics

    manual = {learner_axis} | ({pod_axis} if pod_axis else set())
    batch_spec = P((pod_axis, learner_axis) if pod_axis else learner_axis)
    shard_fn = jax.shard_map(
        per_rank_round, mesh=mesh,
        in_specs=(P(), batch_spec, P(), P(), P(), P()),
        out_specs=(P(), P()),
        axis_names=frozenset(manual), check_vma=False)
    jit_fn = jax.jit(shard_fn, donate_argnums=(0,))

    def round_fn(params, tokens, reservation, weights=None, alive=None):
        epoch, counter = reservation
        if weights is None:
            weights = jnp.ones((n,), jnp.float32)
        if alive is None:
            alive = jnp.ones((n,), jnp.float32)
        with jax.set_mesh(mesh):
            params, metrics = jit_fn(params, tokens, weights,
                                     np.uint32(epoch), np.uint32(counter),
                                     alive)
        return params, jax.tree.map(np.asarray, metrics)

    # the flat delta, plus the weight word of a weighted mean
    payload = tree_size(jax.eval_shape(model.init, jax.random.key(0)))
    return FederatedBundle(round_fn=round_fn, init_state_fn=lambda p: p,
                           round_words=payload + (
                               1 if aggregator.cfg.weighted else 0))


@dataclasses.dataclass
class WireFederated:
    """JAX-side half of wire-plane federated training.

    ``local_fns[node]`` computes that learner's f32[P] delta from the
    current params (standalone jit — no mesh, no shard_map), and
    ``apply_fn`` merges a published average delta; both are exactly what
    :func:`repro.net.client.run_federated_round_net` consumes, keeping
    ``repro.net`` JAX-free (callables are injected, never imported).
    """

    local_fns: Dict[int, Callable[[Any], np.ndarray]]
    apply_fn: Callable[[Any, np.ndarray], Any]
    payload_words: int
    last_losses: Dict[int, float]

    def words_per_round(self, weighted: bool = True) -> int:
        """Counter words one aggregation round consumes (the weighted
        payload appends one weight word) — what a persistent session's
        :class:`~repro.core.session.RoundCursor` must advance by, and
        the in-SPMD plane's ``FederatedBundle.round_words`` for the
        same aggregator."""
        return self.payload_words + (1 if weighted else 0)


def make_wire_federated(
    model: Model,
    tokens_by_learner: Dict[int, np.ndarray],
    *,
    local_steps: int = 4,
    local_lr: float = 1e-3,
) -> WireFederated:
    """Build per-learner local-update callables for the wire runtime.

    ``tokens_by_learner`` maps 1-based node ids (paper numbering — the
    same ids the broker chains carry) to that org's private
    int32[local_steps, B, S] microbatches. Every callable shares ONE
    compiled program (learners differ only in data), so an n-org round
    compiles once.
    """
    local_update = make_local_update(model, local_steps=local_steps,
                                     local_lr=local_lr)
    step = jax.jit(local_update)
    params_abs = jax.eval_shape(model.init, jax.random.key(0))
    psize = tree_size(params_abs)
    losses: Dict[int, float] = {}

    def make_fn(node: int, toks: np.ndarray):
        toks = jnp.asarray(toks)

        def fn(params) -> np.ndarray:
            delta, loss = step(params, toks)
            losses[node] = float(loss)
            return np.asarray(delta, np.float32)

        return fn

    local_fns = {node: make_fn(node, toks)
                 for node, toks in sorted(tokens_by_learner.items())}
    return WireFederated(local_fns=local_fns, apply_fn=apply_delta,
                         payload_words=psize, last_losses=losses)
