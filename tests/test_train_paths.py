"""The one SAFE train step on the model families and meshes it serves.

Every parameter — MoE experts included — goes through the chain, a
vision-language model takes its prefix embeddings, a pod mesh federates
its pods, and ``jit_fn`` keeps the positional contract that ahead-of-time
lowerings (``bench/aot_plan.py``) rely on. Multi-device cases run in a
child process on 8 CPU devices."""
import jax
import pytest

from helpers import run_multidevice
from repro.configs import all_arch_ids, get_smoke_config
from repro.core import make_aggregator
from repro.launch.mesh import make_mesh
from repro.models import Model
from repro.train.flatten import tree_size
from repro.train.train_step import make_train_step

PREAMBLE = """
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_smoke_config
from repro.core import make_aggregator
from repro.launch.mesh import make_mesh
from repro.models import Model
from repro.train.train_step import make_train_step
"""


@pytest.mark.parametrize("arch", all_arch_ids())
def test_the_chain_carries_every_parameter(arch):
    """The chained vector is the whole parameter tree, and the state holds
    only the parameters, the ZeRO-1 master and its moments."""
    model = Model(get_smoke_config(arch))
    mesh = make_mesh((1, 1), ("data", "model"))
    b = make_train_step(model, make_aggregator("insec", 1), mesh)
    assert b.sec_size == tree_size(b.params_abs)
    assert b.padded_size == b.sec_size == b.round_words
    assert set(b.state_shardings) == {"params", "master", "fm", "fv",
                                      "fstep"}
    assert (jax.tree.structure(b.state_shardings["params"])
            == jax.tree.structure(b.params_abs))


def test_moe_safe_step_trains_every_expert():
    out = run_multidevice(PREAMBLE + """
import dataclasses

mesh = make_mesh((4, 2), ("data", "model"))
# f32: in bf16 a freshly-initialized router has near-uniform probs, so the
# codec's rounding of the mean gradient flips bf16 weights and with them
# top-k picks, and SAFE and INSEC part by more than the codec's error
cfg = dataclasses.replace(get_smoke_config("qwen3-moe-235b-a22b"),
                          dtype="float32")
model = Model(cfg)
toks = jnp.asarray(np.random.RandomState(0).randint(
    0, cfg.vocab, (4, 2, 64)).astype(np.int32))

def experts(params):
    found = {}
    for path, x in jax.tree_util.tree_flatten_with_path(params)[0]:
        name = jax.tree_util.keystr(path)
        if "moe" in name and name.endswith(("'wi']", "'wg']", "'wo']")):
            found[name] = np.asarray(x, np.float32)
    return found

def run(mode, steps=3):
    agg = make_aggregator(mode, 4, axis="data")
    b = make_train_step(model, agg, mesh, lr=1e-3)
    s = b.init_state_fn(model.init(jax.random.key(0)))
    before = experts(s["params"])
    assert len(before) >= 3, sorted(before)  # wi, wg, wo of each MoE block
    losses = []
    for i in range(steps):
        s, m = b.step_fn(s, toks, agg.reserve_round(b.round_words))
        losses.append(float(m["loss"]))
        after = experts(s["params"])
        assert after.keys() == before.keys()
        for name, x in after.items():
            assert np.isfinite(x).all(), (mode, i, name)
            assert not np.array_equal(x, before[name]), (mode, i, name)
        before = after
    return losses

safe = run("safe")
insec = run("insec")
assert safe[-1] < safe[0], f"loss not decreasing: {safe}"
assert max(abs(a - b) for a, b in zip(safe, insec)) < 5e-3, (safe, insec)
print("MOE_TRAIN_OK", safe)
""", devices=8)
    assert "MOE_TRAIN_OK" in out


def test_vlm_step_takes_its_prefix():
    out = run_multidevice(PREAMBLE + """

mesh = make_mesh((4, 2), ("data", "model"))
cfg = get_smoke_config("internvl2-1b")
assert cfg.prefix_embeds > 0
model = Model(cfg)
rng = np.random.RandomState(0)
toks = jnp.asarray(rng.randint(0, cfg.vocab, (4, 2, 64)).astype(np.int32))
prefix = jnp.asarray(rng.normal(0, 1, (4, 2, cfg.prefix_embeds, cfg.d_model))
                     .astype(np.float32))
agg = make_aggregator("safe", 4, axis="data")
b = make_train_step(model, agg, mesh, lr=1e-3)
s = b.init_state_fn(model.init(jax.random.key(0)))
losses = []
for i in range(2):
    s, m = b.step_fn(s, toks, agg.reserve_round(b.round_words), prefix=prefix)
    losses.append(float(m["loss"]))
assert np.isfinite(losses).all() and losses[-1] < losses[0], losses
print("VLM_TRAIN_OK", losses)
""", devices=8)
    assert "VLM_TRAIN_OK" in out


def test_pod_mesh_step_federates_the_pods():
    out = run_multidevice(PREAMBLE + """

mesh = make_mesh((2, 4, 1), ("pod", "data", "model"))
cfg = get_smoke_config("internlm2-1.8b")
model = Model(cfg)
toks = jnp.asarray(np.random.RandomState(0).randint(
    0, cfg.vocab, (8, 2, 64)).astype(np.int32))
agg = make_aggregator("safe", 4, axis="data", pod_axis="pod")
b = make_train_step(model, agg, mesh, lr=1e-3, pod_axis="pod")
s = b.init_state_fn(model.init(jax.random.key(0)))
losses = []
for i in range(3):
    s, m = b.step_fn(s, toks, agg.reserve_round(b.round_words))
    losses.append(float(m["loss"]))
assert losses[-1] < losses[0], losses
pods = mesh.devices.reshape(2, -1)
for leaf in jax.tree.leaves(s["params"]):
    held = {sh.device: np.asarray(sh.data) for sh in leaf.addressable_shards}
    first = held[pods[0, 0]]
    for d in pods.reshape(-1):
        np.testing.assert_array_equal(held[d], first)
print("POD_TRAIN_OK", losses)
""", devices=8)
    assert "POD_TRAIN_OK" in out


def test_jit_fn_lowers_with_thirteen_positional_arguments():
    """The argument list ``bench/aot_plan.py`` lowers the step with: the
    state, two scalar placeholders in slots 5 and 6, the batch and the
    per-step inputs; eight outputs, the placeholders scalars again."""
    out = run_multidevice(PREAMBLE + """
from jax.sharding import NamedSharding, PartitionSpec as P

n, rows, seq = 4, 2, 64
mesh = make_mesh((n, 2), ("data", "model"))
b = make_train_step(Model(get_smoke_config("internlm2-1.8b")),
                    make_aggregator("safe", n, axis="data"), mesh)
sh = b.state_shardings
rep = NamedSharding(mesh, P())
shaped = lambda shape, dtype, s=rep: jax.ShapeDtypeStruct(shape, dtype,
                                                          sharding=s)
params = jax.tree.map(lambda x, s: shaped(x.shape, x.dtype, s),
                      b.params_abs, sh["params"])
flat = shaped((b.padded_size,), jnp.float32, sh["master"])
args = (params, flat, flat, flat, shaped((), jnp.int32, sh["fstep"]),
        shaped((), jnp.float32), shaped((), jnp.float32),
        shaped((n, rows, seq), jnp.int32, NamedSharding(mesh, b.batch_spec)),
        shaped((1,), jnp.float32), shaped((n,), jnp.float32),
        shaped((), jnp.uint32), shaped((), jnp.uint32),
        shaped((n,), jnp.float32))
assert len(args) == 13
with jax.set_mesh(mesh):
    lowered = b.jit_fn.lower(*args)
outs = lowered.out_info
assert len(outs) == 8, len(outs)
assert jax.tree.structure(outs[0]) == jax.tree.structure(b.params_abs)
for slot in (5, 6):
    assert (outs[slot].shape, outs[slot].dtype) == ((), jnp.float32), slot
assert outs[1].shape == (b.padded_size,)
donated = [a.donated for a in jax.tree.leaves(lowered.args_info[0])]
assert len(donated) == len(jax.tree.leaves(args))
assert not any(donated[-9:]), "only the state's arrays are donated"
lowered.compile()
print("JIT_FN_CONTRACT_OK")
""", devices=8)
    assert "JIT_FN_CONTRACT_OK" in out
