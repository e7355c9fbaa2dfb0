"""ShapeDtypeStruct stand-ins for every (arch × input-shape) entry point.

No device allocation: these are the abstract arguments ``dryrun.py``
lowers against. Shardings are attached so GSPMD lowers the *production*
layout (params Megatron-TP over 'model', experts over 'data', batch over
'data' (+'pod'), long-context KV sequence-sharded).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.config import ModelConfig
from repro.models.sharding import param_pspecs
from repro.models.transformer import Model
from repro.serve.engine import cache_pspecs

INPUT_SHAPES = {
    "train_4k": dict(seq_len=4096, global_batch=256, kind="train"),
    "prefill_32k": dict(seq_len=32768, global_batch=32, kind="prefill"),
    "decode_32k": dict(seq_len=32768, global_batch=128, kind="decode"),
    "long_500k": dict(seq_len=524288, global_batch=1, kind="decode"),
}


def _sharded(shape, dtype, mesh, spec):
    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=NamedSharding(mesh, spec))


def _with_sharding(tree_abs, specs, mesh):
    return jax.tree.map(
        lambda x, s: _sharded(x.shape, x.dtype, mesh, s), tree_abs, specs)


def use_expert_parallel(cfg: ModelConfig) -> bool:
    """Giant MoEs shard experts over the learner axis (DESIGN.md §3)."""
    return cfg.uses_moe and cfg.moe is not None and cfg.moe.num_experts >= 64


def params_abstract(model: Model, mesh: Mesh):
    """Abstract params with production shardings attached."""
    abs_ = jax.eval_shape(model.init, jax.random.key(0))
    axes_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    specs = param_pspecs(model.cfg, abs_, axes_sizes)
    return _with_sharding(abs_, specs, mesh), specs


def token_shape(cfg: ModelConfig, batch: int, seq: int) -> tuple:
    if cfg.num_codebooks > 1:
        return (batch, seq, cfg.num_codebooks)
    return (batch, seq)


@dataclasses.dataclass
class DryrunSpec:
    """A lowerable entry point: fn + abstract args."""
    fn: Any
    args: tuple
    description: str


def train_spec(arch_cfg: ModelConfig, mesh: Mesh, shape: dict,
               aggregator_mode: str = "safe", pipelined: bool = False,
               subgroups: int = 1, chain_model_sharded: bool = False) -> DryrunSpec:
    """train_4k: the full SAFE train step (shard_map)."""
    from repro.core import make_aggregator
    from repro.train.train_step import make_train_step

    axes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n = axes["data"]
    pods = axes.get("pod", 1)
    pod_axis = "pod" if "pod" in axes else None

    cfg = arch_cfg
    if use_expert_parallel(cfg):
        cfg = dataclasses.replace(cfg, ep_axis="data", ep_ranks=n)
    model = Model(cfg)

    if not chain_model_sharded:
        # The paper-faithful single full-vector chain needs ~4 bytes ×
        # sec_params transient per device; above ~12 GB it cannot exist on
        # a v5e (16 GB HBM) at all, so the giant archs take the
        # model-sharded chain as their *baseline* (16 parallel slice
        # chains — same schedule, same per-link bytes; noted in
        # EXPERIMENTS.md §Dry-run).
        from repro.train.flatten import partition_tree, is_expert_path, tree_size
        p_abs = jax.eval_shape(Model(cfg).init, jax.random.key(0))
        sec_abs, _ = partition_tree(p_abs, lambda p: not is_expert_path(p))
        if tree_size(sec_abs) * 4 > 12e9:
            chain_model_sharded = True

    agg = make_aggregator(aggregator_mode, n, axis="data",
                          pod_axis=pod_axis, pipelined=pipelined,
                          subgroups=subgroups)
    bundle = make_train_step(model, agg, mesh, pod_axis=pod_axis,
                             donate=True, chain_model_sharded=chain_model_sharded)

    params_abs_global = bundle.params_abs
    specs = param_pspecs(cfg, params_abs_global,
                         dict(zip(mesh.axis_names, mesh.devices.shape)))
    params_in = _with_sharding(params_abs_global, specs, mesh)

    B_l = shape["global_batch"] // (n * pods)
    assert B_l >= 1, "global batch too small for the mesh"
    flat_len = n if bundle.leafwise else bundle.padded_size
    flat = _sharded((flat_len,), jnp.float32, mesh, P("data"))
    fstep = jax.ShapeDtypeStruct((), jnp.int32)
    if bundle.leafwise:
        # tree AdamW state for the secure partition, Megatron-sharded
        from repro.train.flatten import partition_tree, is_expert_path
        sec_abs_t, _ = partition_tree(bundle.params_abs,
                                      lambda p: not is_expert_path(p))
        mv_specs = param_pspecs(cfg, sec_abs_t,
                                dict(zip(mesh.axis_names, mesh.devices.shape)))
        sec_state = type(bundle.sec_opt_abs)(
            step=jax.ShapeDtypeStruct((), jnp.int32),
            m=_with_sharding(bundle.sec_opt_abs.m, mv_specs, mesh),
            v=_with_sharding(bundle.sec_opt_abs.v, mv_specs, mesh),
        )
    else:
        sec_state = jax.ShapeDtypeStruct((), jnp.float32)
    if use_expert_parallel(cfg):
        from repro.optim.adamw import AdamW
        from repro.train.flatten import partition_tree, is_expert_path
        _, ep_abs = partition_tree(params_abs_global,
                                   lambda p: not is_expert_path(p))
        ep_opt = AdamW()
        ep_state_abs = jax.eval_shape(ep_opt.init, ep_abs)  # no allocation
        # m/v mirror the expert weight sharding (experts over 'data',
        # expert-ff over 'model') — replicating them over 'model' would
        # cost ~190 GB/device for llama4
        ep_specs_m = param_pspecs(cfg, ep_state_abs.m,
                                  dict(zip(mesh.axis_names,
                                           mesh.devices.shape)))
        ep_state = type(ep_state_abs)(
            step=jax.ShapeDtypeStruct((), jnp.int32),
            m=_with_sharding(ep_state_abs.m, ep_specs_m, mesh),
            v=_with_sharding(ep_state_abs.v, ep_specs_m, mesh),
        )
    else:
        ep_state = jax.ShapeDtypeStruct((), jnp.float32)

    batch_axes = ("pod", "data") if pod_axis else ("data",)
    toks = _sharded(token_shape(cfg, n * pods, shape["seq_len"])[:1] +
                    (B_l,) + token_shape(cfg, 1, shape["seq_len"])[1:],
                    jnp.int32, mesh, P(batch_axes))
    # token_shape(cfg, n*pods, seq)[:1] == (n*pods,)
    if cfg.prefix_embeds:
        prefix = _sharded((n * pods, B_l, cfg.prefix_embeds, cfg.d_model),
                          jnp.bfloat16, mesh, P(batch_axes))
    else:
        prefix = jax.ShapeDtypeStruct((1,), jnp.float32)
    weights = jax.ShapeDtypeStruct((n,), jnp.float32)
    epoch = counter = jax.ShapeDtypeStruct((), jnp.uint32)
    alive = jax.ShapeDtypeStruct((n,), jnp.float32)

    args = (params_in, flat, flat, flat, fstep, ep_state, sec_state, toks,
            prefix, weights, epoch, counter, alive)
    return DryrunSpec(fn=bundle.jit_fn, args=args,
                      description=f"train_step n={n} pods={pods} B_l={B_l} "
                                  f"agg={aggregator_mode}"
                                  f"{'+pipelined' if pipelined else ''}"
                                  f"{'+msharded' if chain_model_sharded else ''}"
                                  f"{f'+g{subgroups}' if subgroups > 1 else ''}")


def prefill_spec(arch_cfg: ModelConfig, mesh: Mesh, shape: dict) -> DryrunSpec:
    axes = dict(zip(mesh.axis_names, mesh.devices.shape))
    pod_axis = "pod" if "pod" in axes else None
    batch_axes = ("pod", "data") if pod_axis else ("data",)
    n_batch_ranks = axes["data"] * axes.get("pod", 1)
    model = Model(arch_cfg)
    params_in, _ = params_abstract(model, mesh)
    B = shape["global_batch"]
    toks = _sharded(token_shape(arch_cfg, B, shape["seq_len"]), jnp.int32,
                    mesh, P(batch_axes))

    if use_expert_parallel(arch_cfg) and B % n_batch_ranks == 0:
        # giant MoEs: manual expert parallelism for prefill too — global
        # routing through a GSPMD gather would all-gather the token matrix
        # per layer (hundreds of GB/device); the manual a2a keeps tokens
        # rank-local (DESIGN.md §3)
        cfg_ep = dataclasses.replace(arch_cfg, ep_axis="data",
                                     ep_ranks=axes["data"])
        model_ep = Model(cfg_ep)
        from repro.train.flatten import is_expert_path, _path_str

        def per_rank(prm, t):
            # t: this rank's [B_local, S] slice of the request batch
            logits, cache = model_ep.prefill(prm, t)
            return logits, cache

        params_abs_plain = jax.eval_shape(model.init, jax.random.key(0))
        p_specs = jax.tree_util.tree_map_with_path(
            lambda p, x: P(None, "data") if is_expert_path(_path_str(p))
            else P(), params_abs_plain)

        def cache_out_spec(leaf):
            nd = len(leaf.shape)
            # batch dim (index 1) is rank-local
            return P(*([None, batch_axes] + [None] * (nd - 2)))

        cache_abs = jax.eval_shape(
            lambda: Model(cfg_ep).init_cache(B // n_batch_ranks,
                                             shape["seq_len"],
                                             prefilled=False))
        cache_specs = jax.tree.map(cache_out_spec, cache_abs)
        logits_spec = P(batch_axes)
        manual = {"data"} | ({"pod"} if pod_axis else set())
        fn = jax.jit(jax.shard_map(
            per_rank, mesh=mesh,
            in_specs=(p_specs, P(batch_axes)),
            out_specs=(logits_spec, cache_specs),
            axis_names=frozenset(manual), check_vma=False))
        toks_lead = _sharded((B,) + token_shape(arch_cfg, 1, shape["seq_len"])[1:],
                             jnp.int32, mesh, P(batch_axes))
        return DryrunSpec(fn=fn, args=(params_in, toks_lead),
                          description=f"prefill B={B} S={shape['seq_len']} "
                                      f"manual-EP")

    args = [params_in, toks]
    if arch_cfg.prefix_embeds:
        prefix = _sharded((B, arch_cfg.prefix_embeds, arch_cfg.d_model),
                          jnp.bfloat16, mesh, P(batch_axes))
        args.append(prefix)
        fn = jax.jit(lambda p, t, pe: model.prefill(p, t, pe))
    else:
        fn = jax.jit(lambda p, t: model.prefill(p, t))
    return DryrunSpec(fn=fn, args=tuple(args),
                      description=f"prefill B={B} S={shape['seq_len']}")


def decode_spec(arch_cfg: ModelConfig, mesh: Mesh, shape: dict) -> DryrunSpec:
    axes = dict(zip(mesh.axis_names, mesh.devices.shape))
    pod_axis = "pod" if "pod" in axes else None
    model = Model(arch_cfg)
    params_in, _ = params_abstract(model, mesh)
    B = shape["global_batch"]
    S = shape["seq_len"]
    cache_abs = jax.eval_shape(
        lambda: model.init_cache(B, S, prefilled=True))
    batch_sharded = B > 1
    seq_axis = None if batch_sharded else "data"
    axes_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    specs = cache_pspecs(cache_abs, batch_sharded, seq_axis,
                         model_size=axes_sizes.get("model", 1))
    if pod_axis and batch_sharded:
        # decode batch over pod×data
        def up(s):
            parts = list(s)
            parts = [("pod", "data") if p == "data" else p for p in parts]
            return P(*parts)
        specs = jax.tree.map(lambda s: up(s), specs,
                             is_leaf=lambda x: isinstance(x, P))
    cache_in = _with_sharding(cache_abs, specs, mesh)
    tok_shape = (B, arch_cfg.num_codebooks) if arch_cfg.num_codebooks > 1 else (B,)
    tok_spec = P(("pod", "data") if pod_axis else "data") if batch_sharded else P()
    toks = _sharded(tok_shape, jnp.int32, mesh, tok_spec)
    # donate the cache: the new cache aliases it (no double-buffering)
    fn = jax.jit(model.decode_step, donate_argnums=(2,))
    return DryrunSpec(fn=fn, args=(params_in, toks, cache_in),
                      description=f"decode B={B} cache={S}"
                                  f"{' seq-sharded' if seq_axis else ''}")


def build_spec(arch_cfg: ModelConfig, mesh: Mesh, shape_name: str,
               **train_kw) -> Optional[DryrunSpec]:
    shape = INPUT_SHAPES[shape_name]
    if shape_name == "long_500k" and not arch_cfg.subquadratic:
        return None  # documented skip (DESIGN.md §5)
    if shape["kind"] == "train":
        return train_spec(arch_cfg, mesh, shape, **train_kw)
    if shape["kind"] == "prefill":
        return prefill_spec(arch_cfg, mesh, shape)
    return decode_spec(arch_cfg, mesh, shape)
