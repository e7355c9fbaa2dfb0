"""Training integration: SAFE-aggregated training on an 8-device mesh.

Checks (in a subprocess): loss decreases, SAFE == INSEC within fixed-point
tolerance, failover mid-training, FedAvg weighted rounds, and the
cross-plane acceptance: a wire-trained FedAvg round (real local steps per
learner, deltas chunk-streamed through the asyncio broker) publishes a
model delta bit-identical to the in-SPMD ``train/federated.py`` round."""
import pytest

from helpers import run_multidevice


def test_safe_training_matches_insec():
    out = run_multidevice("""
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_smoke_config
from repro.models import Model
from repro.core import make_aggregator
from repro.train.train_step import make_train_step
from repro.launch.mesh import make_mesh

mesh = make_mesh((4, 2), ("data", "model"))
cfg = get_smoke_config("internlm2-1.8b")
model = Model(cfg)
toks = np.random.RandomState(0).randint(0, cfg.vocab, (4, 2, 64)).astype(np.int32)

def run(mode, steps=4):
    agg = make_aggregator(mode, 4, axis="data")
    b = make_train_step(model, agg, mesh, lr=1e-3)
    s = b.init_state_fn(model.init(jax.random.key(0)))
    ls = []
    for i in range(steps):
        s, m = b.step_fn(s, jnp.asarray(toks), agg.reserve_round(b.round_words))
        ls.append(float(m["loss"]))
    return ls

safe = run("safe")
insec = run("insec")
assert safe[-1] < safe[0], f"loss not decreasing: {safe}"
assert max(abs(a - b) for a, b in zip(safe, insec)) < 5e-3, (safe, insec)
print("SAFE_TRAIN_OK")
""", devices=8)
    assert "SAFE_TRAIN_OK" in out


def test_training_with_learner_failure():
    out = run_multidevice("""
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_smoke_config
from repro.models import Model
from repro.core import make_aggregator
from repro.train.train_step import make_train_step
from repro.launch.mesh import make_mesh

mesh = make_mesh((4, 2), ("data", "model"))
cfg = get_smoke_config("internlm2-1.8b")
model = Model(cfg)
agg = make_aggregator("safe", 4, axis="data")
b = make_train_step(model, agg, mesh, lr=1e-3)
s = b.init_state_fn(model.init(jax.random.key(0)))
toks = np.random.RandomState(0).randint(0, cfg.vocab, (4, 2, 64)).astype(np.int32)
alive = jnp.array([1., 1., 0., 1.])  # learner 2 dead (progress failover)
losses = []
for i in range(4):
    s, m = b.step_fn(s, jnp.asarray(toks), agg.reserve_round(b.round_words),
                     alive=alive)
    losses.append(float(m["loss"]))
assert losses[-1] < losses[0] and np.isfinite(losses).all()
# initiator failure: rank 0 dead
alive0 = jnp.array([0., 1., 1., 1.])
s, m = b.step_fn(s, jnp.asarray(toks), agg.reserve_round(b.round_words),
                 alive=alive0)
assert np.isfinite(float(m["loss"]))
print("FAILOVER_TRAIN_OK")
""", devices=8)
    assert "FAILOVER_TRAIN_OK" in out


def test_federated_weighted_rounds():
    out = run_multidevice("""
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_smoke_config
from repro.models import Model
from repro.core import make_aggregator
from repro.train.federated import make_federated_round
from repro.launch.mesh import make_mesh

mesh = make_mesh((4, 2), ("data", "model"))
cfg = get_smoke_config("internlm2-1.8b")
model = Model(cfg)
agg = make_aggregator("safe", 4, axis="data", weighted=True)
b = make_federated_round(model, agg, mesh, local_steps=2, local_lr=1e-3)
params = model.init(jax.random.key(0))
toks = np.random.RandomState(0).randint(0, cfg.vocab, (4, 2, 2, 64)).astype(np.int32)
w = jnp.array([1000., 2000., 1500., 500.])
losses = []
for r in range(3):
    params, m = b.round_fn(params, jnp.asarray(toks),
                           agg.reserve_round(b.round_words), weights=w)
    losses.append(float(m["local_loss"]))
assert losses[-1] < losses[0], losses
print("FED_OK")
""", devices=8)
    assert "FED_OK" in out


WIRE_FED_CODE = """
import asyncio
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_smoke_config
from repro.models import Model
from repro.core import make_aggregator
from repro.core.machines import key_derivations
from repro.train.federated import make_federated_round, make_wire_federated
from repro.train.flatten import tree_to_flat
from repro.net import SafeBroker, run_federated_rounds_net

n = {n}
R = {rounds}
from repro.launch.mesh import make_mesh
mesh = make_mesh((n,), ("data",))
cfg = get_smoke_config("internlm2-1.8b")
model = Model(cfg)
agg = make_aggregator("safe", n, axis="data", weighted=True)
b = make_federated_round(model, agg, mesh, local_steps=2, local_lr=1e-3,
                         return_delta=True)
rng = np.random.RandomState(0)
toks = rng.randint(0, cfg.vocab, (n, 2, 2, 64)).astype(np.int32)
w = (1000.0 * (1.0 + np.arange(n))).astype(np.float32)  # private org sizes

wf = make_wire_federated(model, dict((i + 1, toks[i]) for i in range(n)),
                         local_steps=2, local_lr=1e-3)
W = wf.words_per_round(weighted=True)  # counter stride both planes share
assert b.round_words == W, (b.round_words, W)

# in-SPMD reference: R rounds, counter advancing W words per round
p_spmd = model.init(jax.random.key(0))
spmd_deltas = []
for r in range(R):
    slot = agg.reserve_round(b.round_words)
    assert slot == (0, r * W), slot
    p_spmd, m = b.round_fn(p_spmd, jnp.asarray(toks), slot,
                           weights=jnp.asarray(w))
    spmd_deltas.append(np.asarray(m["avg_delta"]))

# wire plane: same seeds, real local steps per learner, the SAME R
# rounds on ONE persistent broker session — deltas chunk-streamed
# through the hop-level streaming combine (P ~ 1.7M words, 256k-word
# chunks), reset_round + RoundCursor between rounds
params = model.init(jax.random.key(0))  # round_fn donated the first tree

async def go():
    broker = SafeBroker(progress_timeout=0.5, monitor_interval=0.1,
                        aggregation_timeout=60.0)
    addr = await broker.start()
    try:
        d0 = key_derivations()
        out = await run_federated_rounds_net(
            params, wf.local_fns, wf.apply_fn, addr, rounds=R, weights=w,
            words_per_round=W, chunk_words=1 << 18)
        return out, key_derivations() - d0
    finally:
        await broker.stop()

(new_params, results), derivs = asyncio.run(go())
assert len(results) == R
for r, res in enumerate(results):
    assert res.stats["aggregation_total"] == 4 * n, (r, res.stats)
    assert res.stats["chunk_frames_in"] > 0, "chunk streaming did not engage"
    assert res.streamed_combines == n - 1, (r, res.streamed_combines)
    assert np.array_equal(spmd_deltas[r], res.average), (
        f"round (r) wire-trained delta diverged from the in-SPMD round")
assert np.array_equal(np.asarray(tree_to_flat(p_spmd)),
                      np.asarray(tree_to_flat(new_params)))
# Round-0 amortization: derivations for R rounds == one round's worth
# (4 per LearnerCrypto + the pair keys each learner's hops touch)
assert derivs <= n * 7, derivs
print("WIRE_FED_BITIDENT_OK")
"""


@pytest.mark.parametrize("n,rounds", [(4, 2), (8, 2)])
def test_wire_round_delta_bit_identical(n, rounds):
    """ISSUE 3/4 acceptance: same seeds ⇒ the wire-trained rounds'
    published model deltas (learners running real local FedAvg steps,
    deltas streamed through the chunk-granular combine over TCP, R
    rounds on ONE persistent broker session with no key re-derivation
    after Round 0) are bit-identical to the in-SPMD
    ``train/federated.py`` rounds — and the §5 message counts hold per
    round. (timeout: R rounds of n-learner local jits + the SPMD loop
    in one subprocess — 2x the default budget so a loaded 2-core box
    doesn't flake the suite; the run itself is ~1 min idle.)"""
    out = run_multidevice(WIRE_FED_CODE.format(n=n, rounds=rounds),
                          devices=n, timeout=1800)
    assert "WIRE_FED_BITIDENT_OK" in out

