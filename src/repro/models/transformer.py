"""Composable decoder: block dispatch + scan-over-units model.

Depth is ``cfg.pattern`` repeated ``cfg.n_units`` times. Parameters (and
decode caches) are stacked per pattern position and the forward pass is a
single ``lax.scan`` over units — compile time and HLO size are
O(len(pattern)), not O(depth).

``shared_attn`` positions (zamba2) use one *unstacked* parameter set
reused at every occurrence (weight sharing), closed over by the scan
body.
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models.layers import (
    attention_apply,
    attention_init,
    attention_init_cache,
    mlp_apply,
    mlp_init,
    mshard,
    rmsnorm,
    rmsnorm_init,
)
from repro.models.moe import moe_apply, moe_init
from repro.models.ssm import (
    mamba2_apply,
    mamba2_init,
    mamba2_init_cache,
    rwkv6_apply,
    rwkv6_init,
    rwkv6_init_cache,
)

Params = Any


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------


def block_init(rng, cfg: ModelConfig, kind: str) -> Params:
    ks = jax.random.split(rng, 2)
    p: dict = {"ln1": rmsnorm_init(cfg.d_model), "ln2": rmsnorm_init(cfg.d_model)}
    if kind == "mamba2":
        p["mamba"] = mamba2_init(ks[0], cfg)
    elif kind == "rwkv6":
        p["rwkv"] = rwkv6_init(ks[0], cfg)
    else:  # attention kinds (incl. shared_attn, *_moe)
        p["attn"] = attention_init(ks[0], cfg)
    if "moe" in kind and cfg.moe is not None:
        p["moe"] = moe_init(ks[1], cfg.d_model, cfg.moe)
    elif kind in ("mamba2", "rwkv6") and not cfg.recurrent_mlp:
        pass  # zamba2-style: recurrent blocks have no channel-mix MLP
    else:
        p["mlp"] = mlp_init(ks[1], cfg.d_model, cfg.d_ff)
    return p


def block_apply(params: Params, x: jax.Array, cfg: ModelConfig, kind: str,
                positions, cache: Optional[dict]):
    """Pre-norm residual block. Returns (x, new_cache, aux_loss)."""
    h = rmsnorm(params["ln1"], x, cfg.norm_eps)
    if kind == "mamba2":
        mix, new_cache = mamba2_apply(params["mamba"], h, cfg, cache)
    elif kind == "rwkv6":
        mix, new_cache = rwkv6_apply(params["rwkv"], h, cfg, cache)
    else:
        mix, new_cache = attention_apply(params["attn"], h, cfg, kind,
                                         positions, cache)
    x = x + mix
    aux = jnp.zeros((), jnp.float32)
    if "moe" in params:
        h = rmsnorm(params["ln2"], x, cfg.norm_eps)
        ff, aux = moe_apply(params["moe"], h, cfg.moe)
    elif "mlp" in params:
        h = rmsnorm(params["ln2"], x, cfg.norm_eps)
        ff = mlp_apply(params["mlp"], h)
    else:  # recurrent block without channel-mix (zamba2)
        ff = jnp.zeros_like(x)
    return x + ff, new_cache, aux


def block_init_cache(cfg: ModelConfig, kind: str, batch: int, seq_len: int,
                     prefilled: bool = True) -> dict:
    if kind == "mamba2":
        c = mamba2_init_cache(cfg, batch)
        if prefilled:
            c = {**c, "pos": jnp.full((batch,), seq_len, jnp.int32)}
        return c
    if kind == "rwkv6":
        c = rwkv6_init_cache(cfg, batch, cfg.d_model)
        if prefilled:
            c = {**c, "pos": jnp.full((batch,), seq_len, jnp.int32)}
        return c
    return attention_init_cache(cfg, kind, batch, seq_len,
                                prefilled=prefilled)


# ---------------------------------------------------------------------------
# Model
# ---------------------------------------------------------------------------


class Model:
    """Functional model: params are explicit pytrees."""

    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg

    # -- init ----------------------------------------------------------------
    def init(self, rng) -> Params:
        cfg = self.cfg
        ks = jax.random.split(rng, len(cfg.pattern) + 3)
        embed_shape = (cfg.num_codebooks, cfg.vocab, cfg.d_model) \
            if cfg.num_codebooks > 1 else (cfg.vocab, cfg.d_model)
        params: dict = {
            "embed": jax.random.normal(ks[0], embed_shape, jnp.float32) * 0.02,
            "final_norm": rmsnorm_init(cfg.d_model),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = jax.random.normal(ks[1], embed_shape,
                                                  jnp.float32) * 0.02
        blocks = []
        shared = None
        for pos, kind in enumerate(cfg.pattern):
            if kind == "shared_attn":
                if shared is None:
                    shared = block_init(ks[2 + pos], cfg, kind)
                # placeholder keeps the stacked-xs structure uniform
                blocks.append({"_shared": jnp.zeros((cfg.n_units,), jnp.float32)})
                continue
            stacked = [block_init(jax.random.fold_in(ks[2 + pos], u), cfg, kind)
                       for u in range(cfg.n_units)]
            blocks.append(jax.tree.map(lambda *xs: jnp.stack(xs), *stacked))
        params["blocks"] = blocks
        if shared is not None:
            params["shared_attn"] = shared
        # store weight matrices in the compute dtype (bf16); norm gains and
        # other vectors stay f32 (the f32 master lives in the ZeRO-1 flat
        # vector). A block's leaves carry a leading stacking dim.
        if cfg.dtype == "bfloat16":
            def store(path, x):
                stacked = getattr(path[0], "key", None) == "blocks"
                return (x.astype(jnp.bfloat16) if x.ndim - stacked >= 2
                        else x)
            params = jax.tree_util.tree_map_with_path(store, params)
        return params

    # -- forward (train / prefill) -------------------------------------------
    def forward(self, params: Params, tokens: jax.Array,
                prefix_embeds: Optional[jax.Array] = None):
        """tokens: int32[B, S] (or [B, S, nc] multi-codebook).
        prefix_embeds: optional f32[B, P, d] from the modality frontend.
        Returns (logits, aux_loss)."""
        cfg = self.cfg
        x = self._embed(params, tokens)
        if prefix_embeds is not None:
            x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
        B, S, _ = x.shape
        positions = jnp.arange(S, dtype=jnp.int32)[None, :].repeat(B, 0)
        x = mshard(x, None, None, None)

        shared = params.get("shared_attn")

        def unit(carry, xs):
            x, aux = carry
            for pos, kind in enumerate(cfg.pattern):
                bp = xs[pos]
                if kind == "shared_attn":
                    bp = shared
                fn = block_apply
                if cfg.remat:
                    fn = jax.checkpoint(
                        functools.partial(block_apply, cfg=cfg, kind=kind),
                        static_argnums=())
                    x, _, a = fn(bp, x, positions=positions, cache=None)
                else:
                    x, _, a = fn(bp, x, cfg, kind, positions, None)
                aux = aux + a
            return (x, aux), None

        (x, aux), _ = jax.lax.scan(unit, (x, jnp.zeros((), jnp.float32)),
                                   tuple(params["blocks"]))
        x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
        logits = self._logits(params, x)
        return logits, aux

    # -- prefill -----------------------------------------------------------
    def prefill(self, params: Params, tokens: jax.Array,
                prefix_embeds: Optional[jax.Array] = None, cache: list = None):
        """Run the prompt through the model, filling the decode caches.
        tokens: int32[B, S]. Returns (last_logits[B, ...], cache) — only
        the final position's logits (full-prompt logits at 32k×vocab would
        dominate HBM for nothing; serving only samples the next token)."""
        cfg = self.cfg
        x = self._embed(params, tokens)
        if prefix_embeds is not None:
            x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
        B, S, _ = x.shape
        if cache is None:
            cache = self.init_cache(B, S, prefilled=False)
        logits, cache = self._run_with_cache(
            params, x, cache,
            jnp.arange(S, dtype=jnp.int32)[None, :].repeat(B, 0),
            last_logit_only=True)
        return logits, cache

    # -- decode ---------------------------------------------------------------
    def decode_step(self, params: Params, tokens: jax.Array, cache: list):
        """tokens: int32[B] (or [B, nc]); cache: stacked caches per pattern
        position. Returns (logits[B, vocab...], new_cache)."""
        cfg = self.cfg
        tok = tokens[:, None] if tokens.ndim == 1 else tokens[:, None, :]
        x = self._embed(params, tok)  # [B, 1, d]
        pos0 = cache[0]["pos"][0]  # [n_units, B] -> [B]; all layers agree
        positions = pos0[:, None].astype(jnp.int32)
        logits, new_cache = self._run_with_cache(params, x, cache, positions,
                                                 last_logit_only=True)
        return logits, new_cache

    def _run_with_cache(self, params: Params, x: jax.Array, cache: list,
                        positions: jax.Array, last_logit_only: bool = False):
        cfg = self.cfg
        shared = params.get("shared_attn")

        def unit(carry, xs):
            x = carry
            new_caches = []
            for pos, kind in enumerate(cfg.pattern):
                bp, bc = xs[2 * pos], xs[2 * pos + 1]
                if kind == "shared_attn":
                    bp = shared
                x, nc, _ = block_apply(bp, x, cfg, kind, positions, bc)
                new_caches.append(nc)
            return x, tuple(new_caches)

        xs = []
        for pos in range(len(cfg.pattern)):
            xs.extend([params["blocks"][pos], cache[pos]])
        x, new_cache = jax.lax.scan(unit, x, tuple(xs))
        if last_logit_only:
            x = x[:, -1:]
        x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
        logits = self._logits(params, x)
        if last_logit_only:
            logits = logits[:, 0]
        return logits, list(new_cache)

    def init_cache(self, batch: int, seq_len: int, prefilled: bool = True):
        """Stacked decode caches, one entry per pattern position."""
        cfg = self.cfg
        caches = []
        for kind in cfg.pattern:
            one = block_init_cache(cfg, kind, batch, seq_len, prefilled)
            caches.append(jax.tree.map(
                lambda x: jnp.broadcast_to(x[None], (cfg.n_units,) + x.shape),
                one))
        return caches

    # -- shared pieces ---------------------------------------------------------
    def _embed(self, params: Params, tokens: jax.Array) -> jax.Array:
        cfg = self.cfg
        emb = params["embed"].astype(jnp.bfloat16 if cfg.dtype == "bfloat16"
                                     else jnp.float32)
        if cfg.num_codebooks > 1:
            # musicgen: sum the per-codebook embeddings
            x = jnp.zeros(tokens.shape[:2] + (cfg.d_model,), emb.dtype)
            for c in range(cfg.num_codebooks):
                x = x + emb[c][tokens[..., c]]
        else:
            x = emb[tokens]
        return x * jnp.asarray(cfg.d_model, x.dtype) ** 0.5

    def _logits(self, params: Params, x: jax.Array) -> jax.Array:
        cfg = self.cfg
        head = params["embed"] if cfg.tie_embeddings else params["lm_head"]
        head = head.astype(x.dtype)
        if cfg.num_codebooks > 1:
            logits = jnp.einsum("bsd,cvd->bscv", x, head)
        else:
            logits = jnp.einsum("bsd,vd->bsv", x, head)
        logits = logits.astype(jnp.float32)
        if cfg.logit_softcap is not None:
            logits = cfg.logit_softcap * jnp.tanh(logits / cfg.logit_softcap)
        return logits
