"""The SAFE train step against its plain reference (``bench/refs/lm_step.py``,
the chip check's own) on 4 virtual devices at tiny widths and seeded weights: the step's loss,
its published mean gradient and the parameters after its update, each
within a limit that states its reason; and a key rotation between steps
compiles nothing."""
import pytest

from helpers import REPO, run_multidevice

CODE = """
import dataclasses, json, sys
sys.path.insert(0, {repo!r})
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_smoke_config
from repro.core import make_aggregator
from repro.launch.compile_cache import COMPILES, watch_compiles
from repro.launch.mesh import make_mesh
from repro.models import Model
from repro.obs import MetricsRegistry
from bench.refs import lm_step
from repro.train.flatten import tree_to_flat
from repro.train.train_step import make_train_step

BITS, LR, N = 24, 1e-3, 4
reg = watch_compiles(MetricsRegistry())
cfg = dataclasses.replace(get_smoke_config("internlm2-1.8b"), dtype="{dtype}")
model = Model(cfg)
mesh = make_mesh((N, 1), ("data", "model"))
agg = make_aggregator("safe", N, axis="data", scale_bits=BITS)
b = make_train_step(model, agg, mesh, lr=LR)
toks = np.random.default_rng(7).integers(0, cfg.vocab, (N, 2, 64),
                                         dtype=np.int32)
params = model.init(jax.random.key(3))
p0 = np.asarray(tree_to_flat(params))
state = b.init_state_fn(params)
state, m = b.step_fn(state, jnp.asarray(toks), agg.reserve_round(b.round_words))
got = np.asarray(state["fm"])[:b.sec_size] / np.float32(0.1)  # m = (1-b1) g
master = np.asarray(state["master"])[:b.sec_size]

widths = {{"hidden_size": cfg.d_model, "num_attention_heads": cfg.n_heads,
          "num_key_value_heads": cfg.n_kv_heads, "rms_norm_eps": cfg.norm_eps,
          "rope_theta": cfg.rope_theta,
          "tie_word_embeddings": cfg.tie_embeddings}}
learner = jax.jit(lambda p, t: lm_step.loss_and_grad(p, t, widths))
out = [learner(model.init(jax.random.key(3)), jnp.asarray(t)) for t in toks]
ref_loss = float(np.mean([float(l) for l, _ in out]))
want = lm_step.clear_mean([np.asarray(tree_to_flat(g)) for _, g in out])
# the reference's AdamW applied to the published gradient
applied = lm_step.adamw_first_step(p0, got, lr=LR)

rms = lambda x: float(np.sqrt(np.mean(np.square(x.astype(np.float64)))))
before = reg.counter(COMPILES).value
agg.resume(9, 2**32 - b.round_words // 2)  # the next step opens epoch 10
slot = agg.reserve_round(b.round_words)
state, _ = b.step_fn(state, jnp.asarray(toks), slot)
print(json.dumps({{
    "loss_rel": abs(float(m["loss"]) - ref_loss) / ref_loss,
    "grad_max_abs": float(np.max(np.abs(got - want))),
    "grad_max": float(np.max(np.abs(want))),
    "grad_rel_rms": rms(got - want) / rms(want),
    "master_max_abs": float(np.max(np.abs(master - applied))),
    "param_max": float(np.max(np.abs(p0))),
    "rotation": list(slot), "compiles": reg.counter(COMPILES).value - before,
}}))
"""

# Limits and their reasons. A word's fixed-point encoding is off by at
# most 2^-(BITS+1) = 2^-25, and so is the mean of 4 such words.
#  float32: the step computes what the reference does, in another order
#    of float32 sums: loss within 1e-5 relative; each gradient word within
#    the codec's 2^-25 plus 1e-5 of the largest word (float32 sums over
#    128 tokens and 2 layers, ~100 roundoffs of 2^-24).
#  bfloat16: weights and activations in bfloat16 (unit roundoff 2^-9):
#    the loss within 2e-3 relative; the gradient's RMS error within 3% of
#    its RMS (a tiny model's gradient is large against the codec's step).
#  master: the ZeRO-1 master after the step is the reference's AdamW of
#    the published gradient up to float32 rounding: two units in the last
#    place of the largest parameter, 2^-22 of it.
LIMITS = {
    "float32": {"loss_rel": 1e-5, "grad_rel_rms": 1e-4},
    "bfloat16": {"loss_rel": 2e-3, "grad_rel_rms": 3e-2},
}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_safe_step_matches_reference(dtype):
    import json
    out = run_multidevice(CODE.format(dtype=dtype, repo=REPO), devices=4)
    r = json.loads(out.strip().splitlines()[-1])
    lim = LIMITS[dtype]
    assert r["loss_rel"] <= lim["loss_rel"], r
    assert r["grad_rel_rms"] <= lim["grad_rel_rms"], r
    if dtype == "float32":
        assert r["grad_max_abs"] <= 2.0**-25 + 1e-5 * r["grad_max"], r
    assert r["master_max_abs"] <= 2.0**-22 * r["param_max"], r
    assert r["rotation"] == [10, 0] and r["compiles"] == 0, r
