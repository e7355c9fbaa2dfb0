"""Topology layer: cross-plane agreement (sim vs device), failover edge
cases, privacy validation, and the host AliveTracker.

The acceptance property: the discrete-event sim and the device data
plane must agree on successor maps and elected initiators for flat,
subgroup, and failover configurations — both planes now read them from
``repro.topology``, and these tests pin the agreement down:

  * pure-function agreement — the device election formula
    (``elect_initiator_local`` with xp=jax.numpy, exactly what
    core/chain.py traces) against the host/sim formula (xp=numpy, what
    core/protocol.py's runner uses);
  * end-to-end agreement — published averages of the two planes compared
    directly for the same failover configurations (subprocess mesh).
"""
import jax.numpy as jnp
import numpy as np
import pytest

from helpers import run_multidevice
from repro.core.protocol import run_safe_round
from repro.core.types import ChainConfig
from repro.topology import (
    AliveTracker,
    HierarchicalTopology,
    RingTopology,
    elect_initiator_local,
    make_topology,
)

TOPOLOGIES = [
    pytest.param(RingTopology(8, 1), id="flat8"),
    pytest.param(RingTopology(12, 3), id="subgroups12x3"),
    pytest.param(RingTopology(9, 3), id="subgroups9x3"),
]


def _alive_patterns(n):
    """All-alive, dead head, dead run, lone survivor per tail group."""
    pats = [np.ones(n, np.float32)]
    a = np.ones(n, np.float32); a[0] = 0
    pats.append(a)
    a = np.ones(n, np.float32); a[2:5] = 0
    pats.append(a)
    a = np.zeros(n, np.float32); a[0] = 1; a[n - 1] = 1
    pats.append(a)
    return pats


class TestCrossPlaneAgreement:
    @pytest.mark.parametrize("topo", TOPOLOGIES)
    def test_successor_maps_agree(self, topo):
        """Device ppermute schedule == sim chain order, rank for rank."""
        perm = dict(topo.ring_permutation())       # device plane schedule
        smap = topo.successor_map()
        for r in range(topo.num_learners):
            assert perm[r] == smap[r]
        for g, chain in topo.group_chains(node_base=1).items():  # sim view
            for i, node in enumerate(chain):
                assert smap[node - 1] + 1 == chain[(i + 1) % len(chain)]

    @pytest.mark.parametrize("topo", TOPOLOGIES)
    def test_elected_initiators_agree(self, topo):
        """jnp (device-traced) and numpy (sim/host) election formulas
        pick the same initiator for every alive pattern × rotation."""
        n, m = topo.num_learners, topo.group_size
        for alive in _alive_patterns(n):
            for rot in range(m):
                host = topo.elect_initiators(alive, rot)
                device = []
                for g in range(topo.subgroups):
                    ga = jnp.asarray(topo.group_alive(alive, g))
                    loc = int(elect_initiator_local(ga, rot, xp=jnp))
                    device.append(g * m + loc)
                # groups with no survivor are degenerate (never run);
                # compare only groups that still have an alive member
                for g in range(topo.subgroups):
                    if topo.group_alive(alive, g).sum() > 0:
                        assert host[g] == device[g], (alive, rot, g)

    def test_hierarchical_delegates_to_pod_rings(self):
        topo = make_topology(4, 1, pods=2)
        assert isinstance(topo, HierarchicalTopology)
        assert topo.num_learners == 8
        # pod-local rings: successor never crosses a pod boundary
        smap = topo.successor_map()
        for r in range(8):
            assert smap[r] // 4 == r // 4
        chains = topo.group_chains(node_base=1)
        assert chains[0][0] == [1, 2, 3, 4]
        assert chains[1][0] == [5, 6, 7, 8]
        alive = np.ones(8, np.float32)
        alive[4] = 0  # pod 1's first rank dead
        inits = topo.elect_initiators(alive)
        assert inits[0] == [0] and inits[1] == [5]

    def test_published_averages_agree_across_planes(self):
        """End-to-end: sim and device publish the same average for flat,
        subgroup, and failover (incl. dead-initiator) configurations."""
        out = run_multidevice("""
import jax, jax.numpy as jnp, numpy as np
from repro.core import make_aggregator
from repro.core.protocol import run_safe_round
from repro.launch.mesh import make_mesh
mesh = make_mesh((8,), ("data",))
rng = np.random.RandomState(0)
n, V = 8, 33
for subgroups, failed in [(1, []), (1, [4, 6]), (1, [1]),
                          (2, [2]), (2, [1])]:
    vals = rng.uniform(-1, 1, (n, V)).astype(np.float32)
    sim = run_safe_round(vals, subgroups=subgroups, failed_nodes=failed,
                         aggregation_timeout=2.0)
    alive = np.ones(n, np.float32)
    alive[[f - 1 for f in failed]] = 0
    agg = make_aggregator("safe", n, subgroups=subgroups)
    dev = np.asarray(agg.aggregate_sharded(mesh, jnp.asarray(vals),
                                           alive=jnp.asarray(alive)))
    err = float(np.abs(sim.average - dev).max())
    assert err < 2e-3, (subgroups, failed, err)
print("CROSS_PLANE_OK")
""", devices=8)
        assert "CROSS_PLANE_OK" in out


class TestFailoverEdgeCases:
    @pytest.mark.parametrize("subgroups", [1, 2])
    def test_dead_initiator_reelection(self, subgroups):
        """§5.4: the elected initiator is dead before the round — the sim
        times out, re-elects, and still publishes the survivor mean."""
        n, V = 8, 5
        vals = np.random.RandomState(1).uniform(-1, 1, (n, V)).astype(np.float32)
        topo = RingTopology(n, subgroups)
        dead = topo.elect_initiators()[0] + 1  # node id of group-0 initiator
        res = run_safe_round(vals, subgroups=subgroups, failed_nodes=[dead],
                             aggregation_timeout=2.0)
        mask = np.ones(n, bool)
        mask[dead - 1] = False
        if subgroups == 1:
            exp = vals[mask].mean(0)
        else:
            m = n // subgroups
            exp = np.mean([vals[g * m:(g + 1) * m][mask[g * m:(g + 1) * m]].mean(0)
                           for g in range(subgroups)], axis=0)
        np.testing.assert_allclose(res.average, exp, atol=2e-3)
        assert res.initiator_elections >= 1

    def test_all_but_one_dead_subgroup_sim(self):
        """A subgroup reduced to one survivor still completes: the lone
        node self-elects and its value is the group average (§5.3/§5.4)."""
        n, V = 8, 4
        vals = np.random.RandomState(2).uniform(-1, 1, (n, V)).astype(np.float32)
        res = run_safe_round(vals, subgroups=2, failed_nodes=[5, 6, 8],
                             aggregation_timeout=2.0)
        exp = np.mean([vals[0:4].mean(0), vals[6]], axis=0)
        np.testing.assert_allclose(res.average, exp, atol=2e-3)

    def test_all_but_one_dead_subgroup_device(self):
        out = run_multidevice("""
import jax, jax.numpy as jnp, numpy as np
from repro.core import make_aggregator
from repro.launch.mesh import make_mesh
mesh = make_mesh((8,), ("data",))
n, V = 8, 21
vals = np.random.RandomState(3).uniform(-1, 1, (n, V)).astype(np.float32)
alive = jnp.array([1, 1, 1, 1, 0, 0, 1, 0], jnp.float32)
agg = make_aggregator("safe", n, subgroups=2)
out = np.asarray(agg.aggregate_sharded(mesh, jnp.asarray(vals), alive=alive))
exp = np.mean([vals[0:4].mean(0), vals[6]], axis=0)
assert np.abs(out - exp).max() < 1e-3
print("LONE_SURVIVOR_DEVICE_OK")
""", devices=8)
        assert "LONE_SURVIVOR_DEVICE_OK" in out

    def test_hierarchical_pod_averages(self):
        """§5.10 sim plane: per-pod rounds averaged at the parent equal
        the mean of pod means — and pod initiators come from the shared
        topology objects."""
        topo = make_topology(4, 1, pods=2)
        n, V = 4, 6
        vals = np.random.RandomState(4).uniform(-1, 1, (8, V)).astype(np.float32)
        pod_avgs = []
        for p in range(2):
            res = run_safe_round(vals[p * n:(p + 1) * n])
            pod_avgs.append(res.average)
        parent = np.mean(pod_avgs, axis=0)
        exp = np.mean([vals[:4].mean(0), vals[4:].mean(0)], axis=0)
        np.testing.assert_allclose(parent, exp, atol=2e-3)
        assert topo.elect_initiators()[0] == [0]
        assert topo.elect_initiators()[1] == [4]


class TestPrivacyValidation:
    def test_chainconfig_minimum_three(self):
        with pytest.raises(ValueError):
            ChainConfig(num_learners=2, mode="safe")
        with pytest.raises(ValueError):
            ChainConfig(num_learners=2, mode="saf")
        ChainConfig(num_learners=2, mode="insec")  # baseline: no bound

    @pytest.mark.parametrize("n,subgroups", [(8, 4), (6, 3), (4, 2)])
    def test_chainconfig_subgroup_privacy(self, n, subgroups):
        with pytest.raises(ValueError):
            ChainConfig(num_learners=n, subgroups=subgroups, mode="safe")

    def test_topology_divisibility(self):
        with pytest.raises(ValueError):
            RingTopology(8, 3)

    def test_sim_runner_delegates_validation(self):
        vals = np.zeros((8, 3), np.float32)
        with pytest.raises(ValueError):
            run_safe_round(vals, subgroups=4)  # groups of 2


class TestAliveTracker:
    def test_strikes_and_compaction(self):
        topo = RingTopology(8, 2)
        trk = AliveTracker(topo, max_strikes=2)
        trk.report_failure(3)
        assert trk.alive()[3] == 1.0  # one strike is not dead yet
        trk.report_failure(3)
        assert trk.alive()[3] == 0.0
        chains = trk.compact_chains(node_base=1)
        assert chains[0] == [1, 2, 3]  # node 4 (rank 3) compacted out
        assert chains[1] == [5, 6, 7, 8]
        assert trk.survivors() == 7
        trk.report_recovery(3)
        assert trk.survivors() == 8

    def test_degraded_group_detection(self):
        topo = RingTopology(8, 2)
        trk = AliveTracker(topo)
        for r in (4, 5):
            trk.report_failure(r)
        assert trk.degraded_groups() == [1]  # 2 alive < privacy bound 3

    def test_election_tracks_deaths(self):
        topo = RingTopology(6, 1)
        trk = AliveTracker(topo)
        assert trk.elect_initiators() == [0]
        trk.report_failure(0)
        assert trk.elect_initiators() == [1]
