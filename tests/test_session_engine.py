"""Multi-session aggregation engine: bit-identity vs. standalone runs,
slot admission/eviction churn, multi-round counter/rotation advance, and
weighted sessions. Runs on an 8-host-device mesh in a subprocess."""
from helpers import run_multidevice

ENGINE_CODE = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.core import ChainConfig, SecureAggregator
from repro.serve import AggregationEngine
from repro.launch.mesh import make_mesh

mesh = make_mesh((8,), ("data",))
n, V, S = 8, 37, 4
rng = np.random.RandomState(0)
cfg = ChainConfig(num_learners=n, mode="safe")
eng = AggregationEngine(mesh, cfg, slots=S, payload_words=V)

# 6 sessions through 4 slots (forces queueing + eviction churn); session
# 0 runs 3 rounds (counter/rotation advance); session 2 has dead ranks
# including the default initiator (rank 0).
sessions = []
for s in range(6):
    sv = rng.uniform(-2, 2, (n, V)).astype(np.float32)
    alive = np.ones(n, np.float32)
    if s == 2:
        alive[[0, 5]] = 0.0
    sessions.append(eng.submit(sv, rounds=3 if s == 0 else 1,
                               provisioning_seed=0xC0FFEE + s,
                               learner_master=0x5EED + 17 * s,
                               alive=alive, rotate0=s))
eng.run_until_done()
assert all(sess.done for sess in sessions), "sessions left unfinished"
assert eng.rounds_completed == 8, eng.rounds_completed

# --- acceptance: batched output bit-identical to standalone runs -------
for s, sess in enumerate(sessions):
    single = SecureAggregator(cfg, 0xC0FFEE + s, 0x5EED + 17 * s)
    for r in range(sess.rounds):
        ctr, rot = r * V, s + r  # what AggSession reserved/rotated
        def per_rank(v, a, ctr=ctr, rot=rot):
            return single.aggregate(v.reshape(-1), ctr, alive=a, rotate=rot)
        f = jax.shard_map(per_rank, mesh=mesh, in_specs=(P("data"), P()),
                          out_specs=P(), axis_names=frozenset({"data"}),
                          check_vma=False)
        with jax.set_mesh(mesh):
            ref = np.asarray(jax.jit(f)(jnp.asarray(sess.values),
                                        jnp.asarray(sess.alive)))
        assert np.array_equal(ref, sess.results[r]), (
            f"session {s} round {r} not bit-identical")

# --- value sanity: published mean == survivor mean ---------------------
for sess in sessions:
    mask = sess.alive > 0
    exp = sess.values[mask].mean(0)
    assert np.abs(sess.results[0] - exp).max() < 1e-3
print("ENGINE_BITIDENTICAL_OK")

# --- weighted sessions -------------------------------------------------
wcfg = ChainConfig(num_learners=n, mode="safe", weighted=True)
weng = AggregationEngine(mesh, wcfg, slots=2, payload_words=V)
w = rng.uniform(1, 10, (n,)).astype(np.float32)
sv = rng.uniform(-2, 2, (n, V)).astype(np.float32)
wsess = weng.submit(sv, weights=w)
weng.run_until_done()
exp = np.average(sv, 0, weights=w)
assert np.abs(wsess.results[0] - exp).max() < 1e-3
print("ENGINE_WEIGHTED_OK")
"""


def test_engine_bit_identity_and_churn():
    out = run_multidevice(ENGINE_CODE, devices=8)
    assert "ENGINE_BITIDENTICAL_OK" in out
    assert "ENGINE_WEIGHTED_OK" in out
