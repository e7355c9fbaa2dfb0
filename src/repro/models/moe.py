"""Mixture-of-Experts MLP with capacity-based dispatch.

Production layout (MaxText/Switch-style, flop-honest):
  1. router top-k over E experts,
  2. sort token→expert assignments, capacity-capped scatter into
     per-expert buffers [E, C, d] (dropped tokens pass through the
     residual unchanged),
  3. batched expert matmuls [E, C, d] × [E, d, ff] — E·C·d·ff flops,
     i.e. top_k/E of the dense-all-experts cost,
  4. weighted scatter-add back.

Expert weights carry the layout of ``models/sharding.py`` (experts over
'data', expert-ff over 'model'); GSPMD derives the dispatch from it. The
auxiliary load-balance loss is returned to the caller (summed into the
train loss).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.layers import _dense_init, mshard


def moe_init(rng, d: int, moe_cfg) -> dict:
    E, ff = moe_cfg.num_experts, moe_cfg.expert_d_ff
    ks = jax.random.split(rng, 5)
    params = {
        "router": _dense_init(ks[0], (d, E), scale=0.02),
        "wi": _dense_init(ks[1], (E, d, ff)),
        "wg": _dense_init(ks[2], (E, d, ff)),
        "wo": _dense_init(ks[3], (E, ff, d)),
    }
    if moe_cfg.num_shared_experts:
        s = moe_cfg.num_shared_experts
        params["shared_wi"] = _dense_init(ks[4], (d, s * ff))
        params["shared_wg"] = _dense_init(ks[4], (d, s * ff))
        params["shared_wo"] = _dense_init(ks[4], (s * ff, d))
    return params


def moe_apply(params: dict, x: jax.Array,
              moe_cfg) -> tuple[jax.Array, jax.Array]:
    """x: [B, S, d] -> (y, aux_loss)."""
    B, S, d = x.shape
    E, k = moe_cfg.num_experts, moe_cfg.top_k
    capacity_factor = moe_cfg.capacity_factor
    T = B * S
    xt = x.reshape(T, d)

    logits = (xt @ params["router"].astype(x.dtype)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, assign = jax.lax.top_k(probs, k)  # [T, k]
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    # Switch aux loss: E * Σ_e (fraction routed to e)·(mean router prob e)
    counts = jnp.zeros((E,), jnp.float32).at[assign.reshape(-1)].add(1.0)
    frac = counts / (T * k)
    aux = E * jnp.sum(frac * probs.mean(0)) * moe_cfg.aux_loss_weight

    # ---- capacity-capped dispatch ----------------------------------------
    C = int(np.ceil(T * k / E * capacity_factor))
    C = max(8, min(C, T))
    flat_assign = assign.reshape(-1)  # [T*k]
    order = jnp.argsort(flat_assign, stable=True)
    sorted_e = flat_assign[order]
    seg_start = jnp.searchsorted(sorted_e, jnp.arange(E))
    pos_in_e = jnp.arange(T * k) - seg_start[sorted_e]
    keep = pos_in_e < C
    # scatter slot ids: dropped entries go to a scratch row
    slot = jnp.where(keep, sorted_e * C + pos_in_e, E * C)
    token_of = order // k
    dispatch_tok = jnp.full((E * C + 1,), T, jnp.int32).at[slot].set(
        token_of.astype(jnp.int32))[: E * C]

    xpad = jnp.concatenate([xt, jnp.zeros((1, d), xt.dtype)], 0)
    xe = xpad[dispatch_tok].reshape(E, C, d)  # [E, C, d] — a2a under GSPMD
    # anchor to the expert-weight layout (experts over 'data', expert-ff
    # over 'model' — models/sharding.py); anchoring E over 'model' here
    # would force a full reshard of the dispatch buffers
    xe = mshard(xe, "data", None, None)

    h = jnp.einsum("ecd,edf->ecf", xe, params["wi"].astype(x.dtype))
    h = h * jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe,
                                   params["wg"].astype(x.dtype)))
    h = mshard(h, "data", None, "model")
    ye = jnp.einsum("ecf,efd->ecd", h, params["wo"].astype(x.dtype))
    ye = mshard(ye, "data", None, None)

    # ---- weighted combine --------------------------------------------------
    gates_sorted = gate_vals.reshape(-1)[order]
    gate_of_slot = jnp.zeros((E * C + 1,), x.dtype).at[slot].set(
        gates_sorted.astype(x.dtype))[: E * C]
    contrib = ye.reshape(E * C, d) * gate_of_slot[:, None]
    y = jnp.zeros((T + 1, d), x.dtype).at[dispatch_tok].add(contrib)[:T]

    if moe_cfg.num_shared_experts:
        hs = (xt @ params["shared_wi"].astype(x.dtype)) * jax.nn.silu(
            xt @ params["shared_wg"].astype(x.dtype))
        y = y + hs @ params["shared_wo"].astype(x.dtype)

    return y.reshape(B, S, d), aux
