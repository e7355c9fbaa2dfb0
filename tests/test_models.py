"""Architecture-zoo smoke tests: reduced variant of each assigned arch,
one forward + one decode step on CPU; shape and finiteness asserted.
Decode/prefill cache consistency for representative families."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import all_arch_ids, get_config, get_smoke_config
from repro.models import Model
from repro.models.sharding import sanitize_spec

ARCHS = all_arch_ids()


@pytest.mark.parametrize("arch", ARCHS)
def test_full_config_validates(arch):
    cfg = get_config(arch)
    assert cfg.n_layers == cfg.n_units * len(cfg.pattern)
    assert cfg.param_count() > 0
    if cfg.uses_moe:
        assert cfg.active_param_count() < cfg.param_count()


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_forward_and_decode(arch):
    cfg = get_smoke_config(arch)
    assert cfg.n_layers <= 2 and cfg.d_model <= 512
    if cfg.moe:
        assert cfg.moe.num_experts <= 4
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    B, S = 2, 64
    rng = jax.random.key(1)
    shape = (B, S, cfg.num_codebooks) if cfg.num_codebooks > 1 else (B, S)
    toks = jax.random.randint(rng, shape, 0, cfg.vocab)
    prefix = None
    if cfg.prefix_embeds:
        prefix = jax.random.normal(rng, (B, cfg.prefix_embeds, cfg.d_model),
                                   jnp.bfloat16)
    logits, aux = jax.jit(model.forward)(params, toks, prefix)
    S_out = S + cfg.prefix_embeds
    if cfg.num_codebooks > 1:
        assert logits.shape == (B, S_out, cfg.num_codebooks, cfg.vocab)
    else:
        assert logits.shape == (B, S_out, cfg.vocab)
    assert bool(jnp.all(jnp.isfinite(logits))), "NaN/inf in forward logits"
    assert bool(jnp.isfinite(aux))

    cache = model.init_cache(B, 32, prefilled=False)
    tok1 = toks[:, 0] if cfg.num_codebooks == 1 else toks[:, 0, :]
    dl, cache2 = jax.jit(model.decode_step)(params, tok1, cache)
    assert bool(jnp.all(jnp.isfinite(dl))), "NaN/inf in decode logits"
    # cache position advanced everywhere
    assert int(cache2[0]["pos"][0, 0]) == 1


@pytest.mark.parametrize("arch", ["internlm2-1.8b", "gemma3-12b",
                                  "rwkv6-1.6b", "zamba2-2.7b",
                                  "qwen3-moe-235b-a22b", "musicgen-large"])
def test_decode_matches_forward(arch):
    """Token-by-token decode must reproduce the teacher-forced forward."""
    cfg = get_smoke_config(arch)
    model = Model(cfg)
    params = model.init(jax.random.key(1))
    B, S = 1, 16
    shape = (B, S, cfg.num_codebooks) if cfg.num_codebooks > 1 else (B, S)
    toks = jax.random.randint(jax.random.key(2), shape, 0, cfg.vocab)
    full, _ = jax.jit(model.forward)(params, toks)
    cache = model.init_cache(B, S, prefilled=False)
    step = jax.jit(model.decode_step)
    scale = float(jnp.max(jnp.abs(full)))
    for t in range(S):
        tok_t = toks[:, t] if cfg.num_codebooks == 1 else toks[:, t, :]
        dl, cache = step(params, tok_t, cache)
        err = float(jnp.max(jnp.abs(dl - full[:, t])))
        assert err / scale < 2e-2, f"pos {t}: rel err {err/scale}"


@pytest.mark.parametrize("arch", ["internlm2-1.8b", "gemma2-27b",
                                  "rwkv6-1.6b", "zamba2-2.7b"])
def test_prefill_then_decode(arch):
    cfg = get_smoke_config(arch)
    model = Model(cfg)
    params = model.init(jax.random.key(3))
    B, S, S0 = 1, 16, 8
    toks = jax.random.randint(jax.random.key(4), (B, S), 0, cfg.vocab)
    full, _ = jax.jit(model.forward)(params, toks)
    scale = float(jnp.max(jnp.abs(full)))
    cache = model.init_cache(B, S, prefilled=False)
    pl, cache = jax.jit(model.prefill)(params, toks[:, :S0], cache=cache)
    assert float(jnp.max(jnp.abs(pl - full[:, S0 - 1]))) / scale < 2e-2
    step = jax.jit(model.decode_step)
    for t in range(S0, S):
        dl, cache = step(params, toks[:, t], cache)
        assert float(jnp.max(jnp.abs(dl - full[:, t]))) / scale < 2e-2


def test_sliding_window_masks_old_tokens():
    """A local layer must not attend beyond its window: far-past token
    perturbations cannot change the output."""
    cfg = get_smoke_config("gemma3-12b")
    cfg = dataclasses.replace(cfg, pattern=("local",), n_layers=1, window=8)
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    toks = jax.random.randint(jax.random.key(1), (1, 32), 0, cfg.vocab)
    toks2 = toks.at[0, 0].set((toks[0, 0] + 1) % cfg.vocab)
    a, _ = jax.jit(model.forward)(params, toks)
    b, _ = jax.jit(model.forward)(params, toks2)
    # positions >= window past the change are unaffected
    np.testing.assert_allclose(np.asarray(a[0, 9:]), np.asarray(b[0, 9:]),
                               atol=1e-6)
    # position 0 itself is affected
    assert float(jnp.max(jnp.abs(a[0, 0] - b[0, 0]))) > 1e-4


def test_moe_capacity_drops_are_bounded():
    """With capacity_factor 1.0 and adversarially unbalanced routing some
    tokens drop, but outputs stay finite and aux loss grows."""
    cfg = get_smoke_config("qwen3-moe-235b-a22b")
    moe = dataclasses.replace(cfg.moe, capacity_factor=1.0)
    cfg = dataclasses.replace(cfg, moe=moe)
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    toks = jnp.zeros((2, 64), jnp.int32)  # identical tokens -> worst routing
    logits, aux = jax.jit(model.forward)(params, toks)
    assert bool(jnp.all(jnp.isfinite(logits)))
    assert float(aux) > 0


def test_zamba2_weight_sharing():
    """shared_attn blocks reuse ONE parameter set."""
    cfg = get_smoke_config("zamba2-2.7b")
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    assert "shared_attn" in params
    # the stacked placeholder for shared positions carries no weights
    for pos, kind in enumerate(cfg.pattern):
        if kind == "shared_attn":
            assert set(params["blocks"][pos].keys()) == {"_shared"}


def test_rwkv6_state_decode_is_constant_memory():
    cfg = get_smoke_config("rwkv6-1.6b")
    model = Model(cfg)
    cache = model.init_cache(2, 10_000, prefilled=True)
    leaves = jax.tree.leaves(cache)
    total = sum(np.prod(np.shape(l)) for l in leaves)
    assert total < 2**22, "rwkv6 cache must be O(1) in sequence length"


def test_norm_gains_stay_float32_when_stacked():
    """Model.init stores weight matrices in bf16 and keeps every norm gain
    in f32, the stacked per-layer gains (2-D) included."""
    cfg = get_smoke_config("internlm2-1.8b")
    params = Model(cfg).init(jax.random.key(0))
    blk = params["blocks"][0]
    assert blk["ln1"]["scale"].ndim == 2
    for gain in (blk["ln1"]["scale"], blk["ln2"]["scale"],
                 params["final_norm"]["scale"]):
        assert gain.dtype == jnp.float32
    for w in (blk["attn"]["wq"], blk["mlp"]["wo"], params["embed"],
              params["lm_head"]):
        assert w.dtype == jnp.bfloat16


def test_internlm2_published_update_size():
    """InternLM2-1.8B at its published widths (untied head, per its
    config.json) cut to 4 layers aggregates 630,736,896 words a step:
    4 x 62,918,656 per layer + 2 x 189,530,112 for the embedding and the
    head + 2,048 for the final norm."""
    from repro.train.flatten import tree_size
    cfg = dataclasses.replace(get_config("internlm2-1.8b"), n_layers=4)
    assert not cfg.tie_embeddings and cfg.norm_eps == 1e-5
    size = tree_size(jax.eval_shape(Model(cfg).init, jax.random.key(0)))
    assert size == 630_736_896 == 4 * 62_918_656 + 2 * 189_530_112 + 2_048


class TestSanitizeSpec:
    def test_drops_non_divisible(self):
        spec = sanitize_spec(P("model", None), (151655, 896), {"model": 16})
        assert spec == P(None, None)

    def test_keeps_divisible(self):
        spec = sanitize_spec(P("model", None), (256, 8), {"model": 16})
        assert spec == P("model", None)

    def test_tuple_axes(self):
        spec = sanitize_spec(P(("pod", "data"), None), (64, 8),
                             {"pod": 2, "data": 16})
        assert spec == P(("pod", "data"), None)
        spec = sanitize_spec(P(("pod", "data"), None), (33, 8),
                             {"pod": 2, "data": 16})
        assert spec == P(None, None)

    def test_pads_short_spec(self):
        spec = sanitize_spec(P("model"), (32, 4, 4), {"model": 16})
        assert spec == P("model", None, None)
