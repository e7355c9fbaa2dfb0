"""Control-plane protocol simulation: correctness, §5 message formulas,
failover, subgroups, weighted averaging, privacy of the broker view."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.costs import DEEP_EDGE, EDGE
from repro.core.protocol import LearnerCrypto, run_safe_round
from repro.core.bon_protocol import run_bon_round


def _vals(n, V, seed=0):
    return np.random.RandomState(seed).uniform(-1, 1, (n, V)).astype(np.float32)


class TestBasicRound:
    def test_safe_average_exact(self):
        vals = _vals(8, 5)
        res = run_safe_round(vals, mode="safe")
        np.testing.assert_allclose(res.average, vals.mean(0), atol=1e-3)

    def test_message_count_4n(self):
        """§5.2: the basic algorithm is exactly 4n messages."""
        for n in (3, 5, 10, 17):
            res = run_safe_round(_vals(n, 3))
            assert res.stats.aggregation_total == 4 * n
            # 1 post_aggregate, 1 check, 1 get_aggregate per node;
            # initiator posts average, others get it
            assert res.stats.post_aggregate == n
            assert res.stats.check_aggregate == n
            assert res.stats.get_aggregate == n
            assert res.stats.post_average == 1
            assert res.stats.get_average == n - 1

    def test_insec_2n_messages(self):
        res = run_safe_round(_vals(6, 3), mode="insec")
        assert res.stats.aggregation_total == 2 * 6

    def test_saf_equals_safe_value(self):
        vals = _vals(7, 4)
        a = run_safe_round(vals, mode="safe").average
        b = run_safe_round(vals, mode="saf").average
        np.testing.assert_allclose(a, b, atol=1e-4)

    def test_min_three_learners(self):
        with pytest.raises(ValueError):
            run_safe_round(_vals(2, 3))
        with pytest.raises(ValueError):
            run_safe_round(_vals(8, 3), subgroups=4)  # groups of 2

    @given(st.integers(3, 12), st.integers(1, 20), st.integers(0, 1000))
    @settings(max_examples=15, deadline=None)
    def test_property_average(self, n, V, seed):
        vals = _vals(n, V, seed)
        res = run_safe_round(vals)
        np.testing.assert_allclose(res.average, vals.mean(0), atol=2e-3)


class TestFailover:
    def test_progress_failover_value_and_messages(self):
        """§5.3: f dead nodes -> average over survivors, 2 extra messages
        per failure, count = 4·(survivors) + 2f."""
        vals = _vals(9, 4)
        res = run_safe_round(vals, mode="safe", failed_nodes=[4, 6])
        mask = np.ones(9, bool)
        mask[[3, 5]] = False
        np.testing.assert_allclose(res.average, vals[mask].mean(0), atol=1e-3)
        assert res.monitor_reposts == 2
        assert res.stats.aggregation_total == 4 * 7 + 2 * 2

    def test_adjacent_failures(self):
        vals = _vals(8, 3)
        res = run_safe_round(vals, mode="safe", failed_nodes=[4, 5, 6])
        mask = np.ones(8, bool)
        mask[[3, 4, 5]] = False
        np.testing.assert_allclose(res.average, vals[mask].mean(0), atol=1e-3)

    def test_drop_to_three_survivors(self):
        """n−f ≥ 3 boundary (§5.3)."""
        vals = _vals(6, 3)
        res = run_safe_round(vals, failed_nodes=[2, 3, 4])
        mask = np.array([1, 0, 0, 0, 1, 1], bool)
        np.testing.assert_allclose(res.average, vals[mask].mean(0), atol=1e-3)

    def test_initiator_failover(self):
        """§5.4: initiator crash -> re-election, average over the rest,
        messages bounded by (i+1)(4n+2f+in)."""
        vals = _vals(10, 3)
        res = run_safe_round(vals, mode="safe", initiator_fails=True,
                             aggregation_timeout=2.0)
        np.testing.assert_allclose(res.average, vals[1:].mean(0), atol=1e-3)
        assert res.initiator_elections == 1
        n, i, f = 10, 1, 0
        assert res.stats.aggregation_total <= (i + 1) * (4 * n + 2 * f + i * n)


class TestSubgroups:
    def test_average_of_group_averages(self):
        vals = _vals(12, 4)
        res = run_safe_round(vals, subgroups=3)
        exp = np.mean([vals[0:4].mean(0), vals[4:8].mean(0),
                       vals[8:12].mean(0)], axis=0)
        np.testing.assert_allclose(res.average, exp, atol=1e-3)

    def test_message_count_4n_plus_g(self):
        """§5.5: one extra get_average per subgroup initiator."""
        res = run_safe_round(_vals(12, 2), subgroups=3)
        assert res.stats.aggregation_total == 4 * 12 + 3

    def test_parallel_groups_faster(self):
        """Subgrouping shortens the serial chain (paper §7.3 evaluates
        this on the deep-edge platform, where per-hop latency dominates
        — Figs. 19-20 show ~4.5s -> ~2s with 4 groups)."""
        vals = _vals(12, 64)
        t1 = run_safe_round(vals, subgroups=1, cost=DEEP_EDGE,
                            symmetric_only=True).virtual_time
        t4 = run_safe_round(vals, subgroups=4, cost=DEEP_EDGE,
                            symmetric_only=True).virtual_time
        assert t4 < 0.7 * t1, (t1, t4)


class TestWeighted:
    def test_weighted_average(self):
        """§5.6: Σwx/Σw without revealing individual weights."""
        vals = _vals(6, 5)
        w = np.array([1000, 200, 3000, 500, 800, 1500], np.float32)
        res = run_safe_round(vals, weights=w)
        np.testing.assert_allclose(res.average,
                                   np.average(vals, 0, weights=w), atol=1e-3)

    def test_no_extra_messages(self):
        base = run_safe_round(_vals(6, 5)).stats.aggregation_total
        w = np.ones(6, np.float32) * 7
        withw = run_safe_round(_vals(6, 5), weights=w).stats.aggregation_total
        assert base == withw


class TestPrivacy:
    def test_broker_never_sees_plaintext(self):
        """Every payload the controller stores must differ from the raw
        encoding (it is masked by R and/or the hop pad)."""
        from repro.core.controller import Controller
        from repro.crypto.np_impl import NpFixedPoint
        vals = _vals(5, 8)
        seen = []
        orig = Controller.post_aggregate

        def spy(self, from_node, to_node, payload, group=0, now=0.0):
            seen.append(np.array(payload))
            return orig(self, from_node, to_node, payload, group, now)

        Controller.post_aggregate = spy
        try:
            run_safe_round(vals, mode="safe")
        finally:
            Controller.post_aggregate = orig
        codec = NpFixedPoint(16)
        encodings = [codec.encode(v) for v in vals]
        partial_sums = []
        acc = np.zeros(8, np.uint32)
        old = np.seterr(over="ignore")
        for e in encodings:
            acc = acc + e
            partial_sums.append(acc.copy())
        np.seterr(**old)
        for payload in seen:
            for plain in encodings + partial_sums:
                assert not np.array_equal(payload, plain), \
                    "controller observed an unmasked (partial) aggregate"

    def test_saf_leaks_nothing_because_of_initiator_mask(self):
        """Even without hop encryption, the single mask R hides partial
        sums from the broker (SAF mode)."""
        from repro.core.controller import Controller
        vals = _vals(4, 6)
        seen = []
        orig = Controller.post_aggregate

        def spy(self, from_node, to_node, payload, group=0, now=0.0):
            seen.append(np.array(payload))
            return orig(self, from_node, to_node, payload, group, now)

        Controller.post_aggregate = spy
        try:
            run_safe_round(vals, mode="saf")
        finally:
            Controller.post_aggregate = orig
        from repro.crypto.np_impl import NpFixedPoint
        codec = NpFixedPoint(16)
        for payload, v in zip(seen, vals):
            assert not np.array_equal(payload, codec.encode(v))


class TestBON:
    def test_bon_average(self):
        vals = _vals(8, 6)
        res = run_bon_round(vals)
        np.testing.assert_allclose(res.average, vals.mean(0), atol=1e-3)

    def test_bon_dropout_recovery(self):
        vals = _vals(9, 4)
        res = run_bon_round(vals, failed_nodes=[3, 7])
        mask = np.ones(9, bool)
        mask[[2, 6]] = False
        np.testing.assert_allclose(res.average, vals[mask].mean(0), atol=1e-3)
        assert res.shares_reconstructed > 0

    def test_bon_quadratic_messages(self):
        """BON share traffic grows with n² (the paper's core complaint)."""
        m10 = run_bon_round(_vals(10, 2)).bytes_sent
        m20 = run_bon_round(_vals(20, 2)).bytes_sent
        assert m20 > 2.5 * m10  # super-linear

    def test_bon_slower_than_safe_at_scale(self):
        """Fig. 6: BON deteriorates by ~15 nodes where SAFE stays linear."""
        vals = _vals(15, 1)
        t_bon = run_bon_round(vals).virtual_time
        t_safe = run_safe_round(vals).virtual_time
        t_insec = run_safe_round(vals, mode="insec").virtual_time
        assert t_bon / t_insec > 5.0
        assert t_safe / t_insec < 5.0


class TestDeepEdge:
    def test_symmetric_only_faster_on_constrained(self):
        """§5.8/§7: pre-negotiated symmetric keys avoid the RSA unwrap
        that dominates on deep-edge hardware."""
        vals = _vals(6, 20)
        hybrid = run_safe_round(vals, cost=DEEP_EDGE, symmetric_only=False)
        prenegotiated = run_safe_round(vals, cost=DEEP_EDGE, symmetric_only=True)
        np.testing.assert_allclose(hybrid.average, prenegotiated.average,
                                   atol=1e-3)
        # pre-negotiation removes one RSA unwrap (~0.35 s on the Archer C7)
        # per hop — 6 hops here
        assert prenegotiated.virtual_time < hybrid.virtual_time - 6 * 0.3


class TestHierarchicalController:
    def test_parent_averages_children(self):
        from repro.core.controller import Controller, HierarchicalController
        import numpy as np
        kids = []
        for base in (0.0, 2.0):
            c = Controller({0: [1, 2, 3]})
            c.post_average(1, np.full(4, base + 1.0), group=0)
            kids.append(c)
        parent = HierarchicalController(kids)
        res = parent.collect()
        np.testing.assert_allclose(res["average"], np.full(4, 2.0))
        assert parent.up_messages == 2

    def test_incomplete_child_rejected(self):
        from repro.core.controller import Controller, HierarchicalController
        parent = HierarchicalController([Controller({0: [1, 2, 3]})])
        with pytest.raises(AssertionError):
            parent.collect()
