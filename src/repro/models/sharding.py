"""Parameter PartitionSpecs (Megatron TP + the MoE expert layout).

Rules are path-based over the tree built by ``Model.init``:

  embed / lm_head       : vocab over 'model'
  attn wq/wk/wv         : [U, d, H·hd]   -> heads over 'model'
  attn wo               : [U, H·hd, d]   -> 'model' on the contracted dim
  mlp wi/wg             : [U, d, ff]     -> ff over 'model'
  mlp wo                : [U, ff, d]     -> 'model' on ff
  moe wi/wg             : [U, E, d, f]   -> experts over 'data', f over 'model'
  moe wo                : [U, E, f, d]   -> experts over 'data', f over 'model'
  mamba in_proj/out_proj, rwkv projections: like mlp
  norms / scalars       : replicated

MoE experts ride the 'data' axis, which gives GSPMD the all-to-all
layout when serving. The train step is manual over 'data' and strips it
from every spec: each learner holds all experts, and their gradients go
through the SAFE chain with every other weight.

``Model.init`` params are replicated over 'data' otherwise: the paper's
cross-org semantics (every learner holds the model) — the ZeRO-1 master
vector in the train step is where 'data'-axis state sharding happens.
"""
from __future__ import annotations

import numpy as np
from jax.sharding import PartitionSpec as P

import jax

from repro.models.config import ModelConfig

def _path_str(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)


_COL = {"wq", "wk", "wv", "wi", "wg", "w_proj", "in_proj",
        "wr", "shared_wi", "shared_wg"}
_ROW = {"wo", "out_proj", "shared_wo"}


def _spec_for(path: str, leaf, cfg: ModelConfig) -> P:
    name = path.rsplit("/", 1)[-1]
    nd = np.ndim(leaf)
    if name in ("embed", "lm_head"):
        # [V, d] or [nc, V, d]
        return P("model", None) if nd == 2 else P(None, "model", None)
    moe = "moe/" in path
    if moe and name in ("wi", "wg", "wo"):
        # [U, E, d/f, f/d]: experts over 'data', expert-ff over 'model'
        if name == "wo":
            return P(None, "data", "model", None)
        return P(None, "data", None, "model")
    if name == "router":
        return P(*([None] * nd))
    if name in _COL and nd >= 2:
        return P(*([None] * (nd - 2)), None, "model")
    if name in _ROW and nd >= 2:
        return P(*([None] * (nd - 2)), "model", None)
    return P(*([None] * nd))


def sanitize_spec(spec: P, shape, axes_sizes: dict) -> P:
    """Drop named axes from dims they don't divide (XLA requires exact
    tiling for explicit input shardings — e.g. internvl2's vocab 151655
    is not divisible by 16)."""
    parts = list(spec) + [None] * (len(shape) - len(spec))
    out = []
    for dim, part in zip(shape, parts):
        if part is None:
            out.append(None)
            continue
        names = part if isinstance(part, tuple) else (part,)
        size = int(np.prod([axes_sizes.get(a, 1) for a in names]))
        out.append(part if dim % size == 0 else None)
    return P(*out)


def param_pspecs(cfg: ModelConfig, params_abs, axes_sizes: dict | None = None):
    """Pytree of PartitionSpec matching ``Model.init``'s structure."""
    def build(p, x):
        spec = _spec_for(_path_str(p), x, cfg)
        if axes_sizes:
            spec = sanitize_spec(spec, np.shape(x), axes_sizes)
        return spec
    return jax.tree_util.tree_map_with_path(build, params_abs)
