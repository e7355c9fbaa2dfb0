"""Wire plane end-to-end: the SAFE state machines over a real asyncio
transport. Acceptance (ISSUE 2): for the same seeds/topology the
published average over the wire is bit-identical to the discrete-event
sim, and MessageStats matches §5's closed forms for n ∈ {4, 8} with and
without an injected failure. Plus: faults (latency/drop/churn),
re-election, the engine plane, the broker's counter hygiene, and the
chunked transfer plane of docs/PROTOCOL.md §6 (boundary sizes,
single-chunk fallback, reordered/duplicate chunks, drops mid-stream,
crash mid-upload).

Every test runs under a hard SIGALRM deadline (autouse fixture) so a
hung broker or lost long-poll aborts the test instead of stalling the
whole tier-1 run.
"""
import asyncio
import signal

import numpy as np
import pytest
from helpers import run_multidevice

from repro.core.protocol import run_safe_round
from repro.net import (
    Chain,
    ChurnInterceptor,
    DropInterceptor,
    LatencyInterceptor,
    SafeBroker,
    run_safe_round_net,
)

#: per-test wall deadline (seconds). The slowest in-process paths below
#: are the re-election tests (~1x aggregation_timeout + a second round);
#: 90 s leaves an order of magnitude of headroom without letting a hang
#: stall tier-1. Tests that spawn a jax subprocess (fresh import +
#: 8-device compile) get the larger budget, aligned with
#: helpers.run_multidevice's own timeout.
NET_TEST_DEADLINE_S = 90
SUBPROCESS_DEADLINE_S = 900
_SUBPROCESS_TESTS = {"test_engine_plane_over_wire"}


@pytest.fixture(autouse=True)
def _hard_deadline(request):
    """Per-test timeout: a hung broker/long-poll raises instead of
    hanging pytest (no pytest-timeout in the container)."""
    deadline = (SUBPROCESS_DEADLINE_S
                if request.node.name in _SUBPROCESS_TESTS
                else NET_TEST_DEADLINE_S)

    def _expired(signum, frame):
        raise TimeoutError(
            f"net test exceeded {deadline}s hard deadline")

    old = signal.signal(signal.SIGALRM, _expired)
    signal.alarm(deadline)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)


def _vals(n, V, seed=0):
    return np.random.RandomState(seed).uniform(-1, 1, (n, V)).astype(np.float32)


def _wire_round(values, *, broker_kw=None, **round_kw):
    """Start a fresh broker, run one round over TCP, tear down."""

    async def go():
        broker = SafeBroker(**dict(
            dict(progress_timeout=0.4, monitor_interval=0.1,
                 aggregation_timeout=30.0), **(broker_kw or {})))
        addr = await broker.start()
        try:
            return await run_safe_round_net(values, addr, **round_kw)
        finally:
            await broker.stop()

    return asyncio.run(go())


class TestSimEquivalence:
    """Same seeds, same topology ⇒ same bits, same message counts."""

    @pytest.mark.parametrize("n", [4, 8])
    def test_bit_identical_no_failure(self, n):
        vals = _vals(n, 16, seed=n)
        sim = run_safe_round(vals)
        net = _wire_round(vals)
        assert np.array_equal(sim.average, net.average)  # bit-identical
        assert net.stats["aggregation_total"] == 4 * n
        assert sim.stats.aggregation_total == 4 * n
        # per-op counters agree too
        for op in ("post_aggregate", "check_aggregate", "get_aggregate",
                   "post_average", "get_average", "should_initiate"):
            assert net.stats[op] == getattr(sim.stats, op), op

    @pytest.mark.parametrize("n", [4, 8])
    def test_bit_identical_with_failure(self, n):
        """One dead learner: §5.3 closed form 4(n−f) + 2f, f=1."""
        vals = _vals(n, 16, seed=10 + n)
        sim = run_safe_round(vals, failed_nodes=[3])
        net = _wire_round(vals, failed_nodes=[3])
        assert np.array_equal(sim.average, net.average)
        expected = 4 * (n - 1) + 2
        assert sim.stats.aggregation_total == expected
        assert net.stats["aggregation_total"] == expected
        assert net.monitor_reposts == 1
        mask = np.ones(n, bool)
        mask[2] = False
        np.testing.assert_allclose(net.average, vals[mask].mean(0), atol=1e-3)

    def test_adjacent_failures(self):
        vals = _vals(8, 8, seed=3)
        sim = run_safe_round(vals, failed_nodes=[4, 5])
        net = _wire_round(vals, failed_nodes=[4, 5])
        assert np.array_equal(sim.average, net.average)
        assert net.stats["aggregation_total"] == 4 * 6 + 2 * 2
        assert net.monitor_reposts == 2

    def test_subgroups_closed_form(self):
        """§5.5: 4n + g messages, average of group averages."""
        vals = _vals(8, 8, seed=4)
        sim = run_safe_round(vals, subgroups=2)
        net = _wire_round(vals, subgroups=2)
        assert np.array_equal(sim.average, net.average)
        assert net.stats["aggregation_total"] == 4 * 8 + 2
        assert sim.stats.aggregation_total == 4 * 8 + 2

    def test_weighted_bit_identical(self):
        vals = _vals(6, 8, seed=5)
        w = np.array([1000, 200, 3000, 500, 800, 1500], np.float32)
        sim = run_safe_round(vals, weights=w)
        net = _wire_round(vals, weights=w)
        assert np.array_equal(sim.average, net.average)
        assert float(sim.weight_avg) == float(net.weight_avg)

    def test_saf_mode(self):
        vals = _vals(5, 8, seed=6)
        sim = run_safe_round(vals, mode="saf")
        net = _wire_round(vals, mode="saf")
        assert np.array_equal(sim.average, net.average)


class TestFaults:
    def test_latency_and_drops_do_not_change_the_answer(self):
        """Transport faults perturb timing, never semantics: the codec +
        retry path must keep the bits and the §5.2 count intact (drops
        happen before the broker sees the frame, so no double count)."""
        vals = _vals(8, 16, seed=7)
        sim = run_safe_round(vals)
        drop = DropInterceptor(p=0.1, seed=3)
        net = _wire_round(vals, interceptor=Chain(
            LatencyInterceptor(mean=0.002, seed=7), drop))
        assert np.array_equal(sim.average, net.average)
        assert net.stats["aggregation_total"] == 4 * 8
        assert drop.dropped > 0  # the fault plan actually fired

    def test_churn_crash_lost_aggregate_reelects(self):
        """A learner crashes *between* consuming the running aggregate
        and reposting it (the worst §5.4 case: the aggregate is lost).
        The round times out, a survivor is re-elected, and the retry
        publishes the survivors' average — bit-identical to a sim where
        that node was dead all along (ring addition commutes)."""
        vals = _vals(8, 16, seed=8)
        churn = ChurnInterceptor({5: 1})  # dies before its post_aggregate
        net = _wire_round(
            vals, interceptor=churn,
            broker_kw=dict(aggregation_timeout=2.0))
        sim = run_safe_round(vals, failed_nodes=[5])
        assert net.crashed_nodes == (5,)
        assert net.initiator_elections >= 1
        assert np.array_equal(sim.average, net.average)

    def test_initiator_crash_reelects(self):
        """Fig. 5: initiator posts once then crashes; §5.4 re-election
        over the wire converges to the survivors' average."""
        vals = _vals(8, 8, seed=9)
        sim = run_safe_round(vals, initiator_fails=True,
                             aggregation_timeout=2.0)
        net = _wire_round(vals, initiator_fails=True,
                          broker_kw=dict(aggregation_timeout=2.0))
        assert net.initiator_elections >= 1
        assert np.array_equal(sim.average, net.average)
        np.testing.assert_allclose(net.average, vals[1:].mean(0), atol=1e-3)


class TestBrokerHygiene:
    def test_unknown_session_is_an_error_not_a_crash(self):
        from repro.net import WireClient, wire as _w

        async def go():
            broker = SafeBroker()
            addr = await broker.start()
            try:
                c = await WireClient(*addr).connect()
                with pytest.raises(_w.WireError, match="unknown session"):
                    await c.request("get_stats", {"session": 999})
                # unserviceable sessions refused at the boundary
                with pytest.raises(_w.WireError, match="empty chain"):
                    await c.request("create_session",
                                    {"groups": {0: [1, 2, 3], 1: []}})
                # connection still serves after the errors
                made = await c.request("create_session",
                                       {"groups": {0: [1, 2, 3]}})
                assert made["session"] == 0
                await c.close()
            finally:
                await broker.stop()

        asyncio.run(go())

    def test_completed_rounds_free_their_sessions(self):
        """run_safe_round_net deletes its broker session: a long-lived
        broker must not accumulate one Controller per finished round."""
        from repro.net import WireClient, wire as _w

        async def go():
            broker = SafeBroker(progress_timeout=0.4, monitor_interval=0.1)
            addr = await broker.start()
            try:
                await run_safe_round_net(_vals(4, 4), addr)
                assert broker._sessions == {}  # torn down server-side
                c = await WireClient(*addr).connect()
                with pytest.raises(_w.WireError, match="unknown session"):
                    await c.request("get_stats", {"session": 0})
                await c.close()
            finally:
                await broker.stop()

        asyncio.run(go())

    def test_stop_unparks_forever_long_polls(self):
        """broker.stop() must cancel connection handlers parked on a
        timeout=None long-poll instead of leaking (or hanging
        wait_closed on newer Pythons)."""
        from repro.net import WireClient

        async def go():
            broker = SafeBroker()
            addr = await broker.start()
            c = await WireClient(*addr).connect()
            await c.request("create_session", {"groups": {0: [1, 2, 3]}})
            poll = asyncio.ensure_future(c.request(
                "get_average", {"session": 0, "timeout": None}))
            await asyncio.sleep(0.2)  # let it park on the broker
            assert not poll.done()
            await broker.stop()  # must return promptly
            with pytest.raises(Exception):
                await asyncio.wait_for(poll, 5.0)  # conn dropped cleanly
            await c.close()

        asyncio.run(go())

    def test_stray_to_node_rejected_and_monitor_survives(self):
        """A posting addressed outside the chain is refused at the RPC
        boundary (it could never be consumed or reposted around), so it
        can't poison the §5.3 monitor for other tenants."""
        from repro.net import WireClient, wire as _w

        async def go():
            broker = SafeBroker(progress_timeout=0.2, monitor_interval=0.05)
            addr = await broker.start()
            try:
                c = await WireClient(*addr).connect()
                await c.request("create_session", {"groups": {0: [1, 2, 3]}})
                with pytest.raises(_w.WireError, match="not in"):
                    await c.request("post_aggregate", {
                        "session": 0, "from_node": 1, "to_node": 99,
                        "group": 0,
                        "payload": np.zeros(4, np.uint32)})
                with pytest.raises(_w.WireError, match="unknown group"):
                    await c.request("post_aggregate", {
                        "session": 0, "from_node": 1, "to_node": 2,
                        "group": 7,
                        "payload": np.zeros(4, np.uint32)})
                await c.close()
                # monitor still alive and clean; a full round still works
                res = await run_safe_round_net(_vals(4, 4), addr)
                assert res.stats["aggregation_total"] == 4 * 4
                assert broker.monitor_errors == 0
            finally:
                await broker.stop()

        asyncio.run(go())

    def test_wire_round_rejects_insec(self):
        with pytest.raises(ValueError):
            _wire_round(_vals(4, 4), mode="insec")

    def test_two_sessions_are_isolated(self):
        """Two tenants on one broker: independent controllers, stats,
        and averages (the multi-session story at the wire level)."""
        vals_a, vals_b = _vals(4, 8, seed=11), _vals(4, 8, seed=12)

        async def go():
            broker = SafeBroker(progress_timeout=0.4, monitor_interval=0.1)
            addr = await broker.start()
            try:
                a, b = await asyncio.gather(
                    run_safe_round_net(vals_a, addr),
                    run_safe_round_net(vals_b, addr, learner_master=0x9999))
            finally:
                await broker.stop()
            return a, b

        a, b = asyncio.run(go())
        sim_a = run_safe_round(vals_a)
        sim_b = run_safe_round(vals_b, learner_master=0x9999)
        assert np.array_equal(a.average, sim_a.average)
        assert np.array_equal(b.average, sim_b.average)
        assert a.stats["aggregation_total"] == 4 * 4
        assert b.stats["aggregation_total"] == 4 * 4


class TestChunkedTransfer:
    """docs/PROTOCOL.md §6: multi-frame array streaming. Chunking is
    transport — bits, §5 message counts and failover semantics must be
    indistinguishable from the unchunked path."""

    def test_multi_chunk_bit_identical_and_counts(self):
        """V=103 over 16-word chunks (7 per transfer, ragged tail)."""
        vals = _vals(6, 103, seed=21)
        sim = run_safe_round(vals)
        # stream=False pins the buffered chunk plane: with the default
        # auto policy a payload this small skips chunking wholesale
        # (ISSUE 9 small-n fast path, TestAutoStreamThreshold)
        net = _wire_round(vals, chunk_words=16, stream=False)
        assert np.array_equal(sim.average, net.average)
        assert net.stats["aggregation_total"] == 4 * 6
        assert net.stats["transfers_completed"] == 7  # 6 hops + average
        assert net.stats["chunk_frames_in"] == 7 * 7

    def test_exact_chunk_boundary(self):
        """V an exact multiple of chunk_words: no empty trailing chunk."""
        vals = _vals(4, 64, seed=22)
        sim = run_safe_round(vals)
        net = _wire_round(vals, chunk_words=16, stream=False)
        assert np.array_equal(sim.average, net.average)
        assert net.stats["chunk_frames_in"] == 5 * 4  # exactly 64/16 each

    def test_single_chunk_fallback(self):
        """Payload fits one chunk: the plain ops carry it, zero chunk
        frames on the wire."""
        vals = _vals(4, 8, seed=23)
        sim = run_safe_round(vals)
        net = _wire_round(vals, chunk_words=16)
        assert np.array_equal(sim.average, net.average)
        assert net.stats["chunk_frames_in"] == 0
        assert net.stats["chunk_frames_out"] == 0

    def test_chunked_weighted_and_dead_node(self):
        """A dead learner's chunked transfer is reposted around (§5.3)
        and the weighted closed form 4(n−f)+2f still holds."""
        vals = _vals(8, 48, seed=24)
        w = np.arange(1, 9, dtype=np.float32) * 100
        sim = run_safe_round(vals, failed_nodes=[3], weights=w)
        net = _wire_round(vals, failed_nodes=[3], weights=w, chunk_words=16,
                          stream=False)
        assert np.array_equal(sim.average, net.average)
        assert float(sim.weight_avg) == float(net.weight_avg)
        assert net.stats["aggregation_total"] == 4 * 7 + 2
        assert net.monitor_reposts == 1

    def test_dropped_chunks_retry_clean(self):
        """Drops hit individual chunk frames (they never reached the
        broker — at-most-once retry), bits and counts survive."""
        vals = _vals(8, 48, seed=25)
        sim = run_safe_round(vals)
        drop = DropInterceptor(p=0.1, seed=9)
        net = _wire_round(vals, chunk_words=16, stream=False,
                          interceptor=Chain(
                              LatencyInterceptor(mean=0.001, seed=9), drop))
        assert np.array_equal(sim.average, net.average)
        assert net.stats["aggregation_total"] == 4 * 8
        assert drop.dropped > 0

    def test_crash_mid_upload_reelects(self):
        """Buffered path (stream=False): a learner dies partway through
        streaming its aggregate AFTER consuming its predecessor's
        posting (the buffered pipeline consumes before it re-posts): no
        stuck posting exists, so §5.3 cannot fire — the round times
        out, §5.4 re-elects, and the survivors' retry publishes,
        bit-identical to a sim where that node was dead all along."""
        vals = _vals(8, 48, seed=26)
        # node 5 (non-initiator): 3 get_chunk + 1 get_aggregate frames,
        # then dies before its 2nd post_chunk — one chunk buffered
        churn = ChurnInterceptor({5: 5})
        net = _wire_round(vals, chunk_words=16, interceptor=churn,
                          stream=False,
                          broker_kw=dict(aggregation_timeout=2.0))
        sim = run_safe_round(vals, failed_nodes=[5])
        assert net.crashed_nodes == (5,)
        assert net.initiator_elections >= 1
        assert np.array_equal(sim.average, net.average)

    def test_crash_mid_streamed_combine_reposts_around(self):
        """Streaming path: the combine defers the logical consume to
        after the upload, so a learner crashing mid-hop leaves its
        predecessor's posting unconsumed — the §5.3 monitor reposts
        around the dead node (no full §5.4 round restart needed), its
        half-combined upload goes stale and is replaced, and the
        survivors' average is bit-identical to a sim where that node
        was dead all along."""
        vals = _vals(8, 48, seed=26)
        churn = ChurnInterceptor({5: 5})  # dies mid-streamed-combine
        net = _wire_round(vals, chunk_words=16, interceptor=churn,
                          stream=True,
                          broker_kw=dict(aggregation_timeout=2.0))
        sim = run_safe_round(vals, failed_nodes=[5])
        assert net.crashed_nodes == (5,)
        assert net.monitor_reposts >= 1
        assert np.array_equal(sim.average, net.average)

    def test_reordered_duplicate_chunks_and_streaming(self):
        """Raw frames: chunks arrive out of order with a duplicate; the
        logical post fires exactly once, on completion; a chunk is
        downloadable *before* the upload completes (store-and-forward
        pipelining); the elided consume counts once."""
        from repro.net import WireClient

        payload = np.arange(40, dtype=np.uint32)
        cw = 16  # chunks [0:16] [16:32] [32:40], total 3

        def frame(seq):
            return {"session": 0, "op": "post_aggregate", "xfer": 77,
                    "seq": seq, "total": 3, "chunk_words": cw,
                    "from_node": 1, "to_node": 2, "group": 0,
                    "payload": payload[seq * cw:(seq + 1) * cw]}

        async def go():
            broker = SafeBroker()
            addr = await broker.start()
            try:
                c = await WireClient(*addr).connect()
                await c.request("create_session", {"groups": {0: [1, 2]}})
                r = await c.request("post_chunk", frame(2))  # tail first
                assert not r["complete"] and r["received"] == 1
                # streaming: the buffered chunk serves before completion
                g = await c.request("get_chunk", {
                    "session": 0, "kind": "get_aggregate", "node": 2,
                    "group": 0, "seq": 2, "words": cw, "timeout": 5.0})
                assert g["last"] and g["from_node"] == 1
                assert np.array_equal(g["payload"], payload[32:])
                st = await c.request("get_stats", {"session": 0})
                assert st["post_aggregate"] == 0  # not a message yet
                await c.request("post_chunk", frame(0))
                r = await c.request("post_chunk", frame(0))  # duplicate
                assert r["received"] == 2  # idempotent overwrite
                r = await c.request("post_chunk", frame(1))
                assert r["complete"]
                # at-least-once repeat AFTER completion (final ack lost):
                # idempotent re-ack, no fresh buffer, no second posting
                r = await c.request("post_chunk", frame(1))
                assert r["complete"] and r["received"] == 3
                st = await c.request("get_stats", {"session": 0})
                assert st["post_aggregate"] == 1
                assert st["transfers_completed"] == 1
                parts = [(await c.request("get_chunk", {
                    "session": 0, "kind": "get_aggregate", "node": 2,
                    "group": 0, "seq": s, "words": cw,
                    "timeout": 5.0}))["payload"] for s in range(3)]
                res = await c.request("get_aggregate", {
                    "session": 0, "node": 2, "group": 0,
                    "elide_payload": True, "timeout": 5.0})
                assert res["chunked"] is True and res["aggregate"] is None
                assert np.array_equal(np.concatenate(parts), payload)
                st = await c.request("get_stats", {"session": 0})
                assert st["get_aggregate"] == 1
                # post_average idempotency: the posted buffer is the
                # repeat record — a re-sent final chunk re-acks, never
                # re-executes the op (PROTOCOL.md §6 repeat rule)
                avg_frame = {"session": 0, "op": "post_average",
                             "xfer": 5, "seq": 0, "total": 1,
                             "chunk_words": cw, "node": 1, "group": 0,
                             "payload": np.zeros(8, np.float32)}
                r = await c.request("post_chunk", dict(avg_frame))
                assert r["complete"]
                r = await c.request("post_chunk", dict(avg_frame))
                assert r["complete"]
                st = await c.request("get_stats", {"session": 0})
                assert st["post_average"] == 1
                await c.close()
            finally:
                await broker.stop()

        asyncio.run(go())


class TestStreamingCombine:
    """The chunk-granular §5.1.2 combine (ISSUE 4 tentpole): chunk k is
    decrypted/added/re-encrypted and shipped downstream while chunk k+1
    is in flight. Streaming is transport scheduling — bits, §5 message
    counts and failover semantics must be indistinguishable from the
    reassemble-then-combine path (and from the sim)."""

    @pytest.mark.parametrize("n", [4, 8])
    def test_streamed_bit_identical_and_counts(self, n):
        vals = _vals(n, 103, seed=30 + n)
        sim = run_safe_round(vals)
        net = _wire_round(vals, chunk_words=16, stream=True)
        assert np.array_equal(sim.average, net.average)
        assert net.stats["aggregation_total"] == 4 * n
        # every non-initiator hop ran the fused streaming combine
        assert net.streamed_combines == n - 1
        for op in ("post_aggregate", "check_aggregate", "get_aggregate",
                   "post_average", "get_average", "should_initiate"):
            assert net.stats[op] == getattr(sim.stats, op), op

    def test_streamed_equals_buffered(self):
        """stream=True vs stream=False: identical bits, counts, and
        chunk-frame tallies (streaming reorders frames, never adds)."""
        vals = _vals(6, 103, seed=31)
        on = _wire_round(vals, chunk_words=16, stream=True)
        off = _wire_round(vals, chunk_words=16, stream=False)
        assert np.array_equal(on.average, off.average)
        assert on.stats["aggregation_total"] == off.stats["aggregation_total"]
        assert on.stats["chunk_frames_in"] == off.stats["chunk_frames_in"]
        assert on.streamed_combines == 5 and off.streamed_combines == 0

    def test_streamed_weighted_with_failure_closed_form(self):
        vals = _vals(8, 48, seed=32)
        w = np.arange(1, 9, dtype=np.float32) * 100
        sim = run_safe_round(vals, failed_nodes=[3], weights=w)
        net = _wire_round(vals, failed_nodes=[3], weights=w, chunk_words=16,
                          stream=True)
        assert np.array_equal(sim.average, net.average)
        assert float(sim.weight_avg) == float(net.weight_avg)
        assert net.stats["aggregation_total"] == 4 * 7 + 2
        assert net.monitor_reposts == 1

    def test_streamed_under_faults(self):
        """Latency + drops against the streaming path: chunk frames are
        retried at-most-once, identities keep assembly straight."""
        vals = _vals(8, 48, seed=33)
        sim = run_safe_round(vals)
        drop = DropInterceptor(p=0.08, seed=11)
        net = _wire_round(vals, chunk_words=16, stream=True,
                          interceptor=Chain(
                              LatencyInterceptor(mean=0.001, seed=11),
                              drop))
        assert np.array_equal(sim.average, net.average)
        assert net.stats["aggregation_total"] == 4 * 8
        assert drop.dropped > 0

    @pytest.mark.parametrize("depth", [1, 4])
    def test_prefetch_depth_is_transport_only(self, depth):
        """Any prefetch depth yields the same bits and counts — depth
        moves wall-clock, never semantics (the ablation that picked
        wire.DEFAULT_PREFETCH_DEPTH lives in benchmarks/streaming.py)."""
        vals = _vals(6, 103, seed=34)
        sim = run_safe_round(vals)
        net = _wire_round(vals, chunk_words=16, prefetch_depth=depth,
                          stream=True)
        assert np.array_equal(sim.average, net.average)
        assert net.stats["aggregation_total"] == 4 * 6
        assert net.streamed_combines == 5


class TestPersistentSessions:
    """One broker session, R rounds (ISSUE 4): reset_round + RoundCursor
    counter bases between rounds, key material and connections reused —
    no key re-derivation after Round 0, per-round §5 closed forms, and
    crash-resume across round boundaries."""

    def test_five_rounds_bit_identical_no_rederivation(self):
        from repro.core import machines
        from repro.net import PersistentNetSession

        n, V, R = 4, 103, 5
        rng = np.random.RandomState(40)
        rounds = [rng.uniform(-1, 1, (n, V)).astype(np.float32)
                  for _ in range(R)]

        async def go():
            broker = SafeBroker(progress_timeout=0.4, monitor_interval=0.1,
                                aggregation_timeout=30.0)
            addr = await broker.start()
            try:
                sess = PersistentNetSession(addr, n, chunk_words=16,
                                            stream=True)
                await sess.open()
                try:
                    d0 = machines.key_derivations()
                    out = []
                    derivs = []
                    for vals in rounds:
                        out.append(await sess.run_round(vals))
                        derivs.append(machines.key_derivations() - d0)
                    assert len(broker._sessions) == 1  # ONE tenant alive
                finally:
                    await sess.close()
                assert broker._sessions == {}  # torn down on close
                return out, derivs
            finally:
                await broker.stop()

        out, derivs = asyncio.run(go())
        # Round 0 derived everything; rounds 1..R-1 derived NOTHING
        assert derivs[0] > 0
        assert all(d == derivs[0] for d in derivs[1:]), derivs
        V_words = rounds[0].shape[1]
        for r, res in enumerate(out):
            sim = run_safe_round(rounds[r], counter=r * V_words)
            assert np.array_equal(sim.average, res.average), f"round {r}"
            # per-round stats delta still satisfies the closed form
            assert res.stats["aggregation_total"] == 4 * n, (r, res.stats)
            assert res.initiator_elections == 0
            assert res.streamed_combines == n - 1

    def test_undersized_counter_stride_is_refused(self):
        """A payload wider than the session's words/round stride would
        overlap the next round's pad words — silent keystream reuse.
        The session must refuse the round up front, even when
        words_per_round was pinned explicitly."""
        from repro.net import PersistentNetSession

        n, V = 4, 32

        async def go():
            broker = SafeBroker()
            addr = await broker.start()
            try:
                # stride sized for unweighted rounds; a weighted round
                # needs V+1 words
                sess = PersistentNetSession(addr, n, words_per_round=V)
                await sess.open()
                try:
                    with pytest.raises(ValueError, match="stride"):
                        await sess.run_round(
                            _vals(n, V), weights=np.ones(n, np.float32))
                    # a correctly-sized round still runs afterwards
                    res = await sess.run_round(_vals(n, V, seed=60))
                    assert res.average is not None
                finally:
                    await sess.close()
            finally:
                await broker.stop()

        asyncio.run(go())

    def test_crash_resume_across_round_boundary(self):
        """Node 5 churn-crashes mid-round-0 (partial streamed combine),
        resumes in round 1: round 0 publishes the survivors' mean
        (§5.3/§5.4 recovery), round 1 is clean — full average, clean
        closed form — over the SAME session and fresh counter space."""
        from repro.net import PersistentNetSession

        n, V = 8, 48
        rng = np.random.RandomState(41)
        vals0 = rng.uniform(-1, 1, (n, V)).astype(np.float32)
        vals1 = rng.uniform(-1, 1, (n, V)).astype(np.float32)
        churn = ChurnInterceptor({5: 5})

        async def go():
            broker = SafeBroker(progress_timeout=0.4, monitor_interval=0.1,
                                aggregation_timeout=2.0)
            addr = await broker.start()
            try:
                sess = PersistentNetSession(addr, n, chunk_words=16,
                                            stream=True, interceptor=churn)
                await sess.open()
                try:
                    r0 = await sess.run_round(vals0)
                    # the org comes back online for the next round
                    churn.crash_after.pop(5)
                    r1 = await sess.run_round(vals1)
                finally:
                    await sess.close()
                return r0, r1
            finally:
                await broker.stop()

        r0, r1 = asyncio.run(go())
        assert r0.crashed_nodes == (5,)
        sim0 = run_safe_round(vals0, failed_nodes=[5])
        assert np.array_equal(sim0.average, r0.average)
        # round 1: node 5 resumed — full clean round on the same session
        assert r1.crashed_nodes == ()
        sim1 = run_safe_round(vals1, counter=V)
        assert np.array_equal(sim1.average, r1.average)
        assert r1.stats["aggregation_total"] == 4 * n

    def test_reset_mid_stream_cannot_corrupt(self):
        """Races against a partially-combined transfer buffer, raw
        frames: (a) reset_round mid-upload — the leftover chunks must
        not complete into a posting; (b) the uploader's own NEWER xfer
        replaces its abandoned stream; (c) a stale frame of the OLD
        xfer after the replacement is discarded, never merged."""
        from repro.net import WireClient

        payload = np.arange(48, dtype=np.uint32)
        cw = 16  # 3 chunks

        def frame(xfer, seq, arr=payload):
            return {"session": 0, "op": "post_aggregate", "xfer": xfer,
                    "seq": seq, "total": 3, "chunk_words": cw,
                    "from_node": 1, "to_node": 2, "group": 0,
                    "payload": arr[seq * cw:(seq + 1) * cw]}

        async def go():
            broker = SafeBroker()
            addr = await broker.start()
            try:
                c = await WireClient(*addr).connect()
                await c.request("create_session", {"groups": {0: [1, 2]}})
                # (a) two chunks up, then the round resets
                await c.request("post_chunk", frame(7, 0))
                await c.request("post_chunk", frame(7, 2))
                await c.request("reset_round", {"session": 0})
                r = await c.request("post_chunk", frame(7, 1))
                # the buffer restarted from scratch: one chunk, no post
                assert not r["complete"] and r["received"] == 1
                st = await c.request("get_stats", {"session": 0})
                assert st["post_aggregate"] == 0
                # (b) the uploader restarts under a newer xfer: replaces
                # its own half-dead stream even though it is "active"
                fresh = np.arange(100, 148, dtype=np.uint32)
                r = await c.request("post_chunk", frame(8, 0, fresh))
                assert r["received"] == 1
                # (c) stale duplicate of the OLD stream: discarded
                r = await c.request("post_chunk", frame(7, 2))
                assert r.get("superseded") and not r["complete"]
                r = await c.request("post_chunk", frame(8, 1, fresh))
                r = await c.request("post_chunk", frame(8, 2, fresh))
                assert r["complete"]
                st = await c.request("get_stats", {"session": 0})
                assert st["post_aggregate"] == 1
                # the posting holds the NEW stream's bytes, untouched by
                # the stale frame
                got = await c.request("get_aggregate", {
                    "session": 0, "node": 2, "group": 0, "timeout": 5.0})
                assert np.array_equal(got["aggregate"], fresh)
                await c.close()
            finally:
                await broker.stop()

        asyncio.run(go())

    def test_federated_rounds_one_session(self):
        """run_federated_rounds_net (ISSUE 4 acceptance): R=5 FedAvg
        rounds on ONE session — no key re-derivation after Round 0, a
        mid-training dead round recovered via §5.3, state evolution
        matching the closed-form FedAvg recursion."""
        from repro.core import machines
        from repro.net import run_federated_rounds_net

        n, P, R = 4, 103, 5
        rng = np.random.RandomState(42)
        grads = {node: rng.uniform(-1, 1, P).astype(np.float32)
                 for node in range(1, n + 1)}
        # each learner's "local update": a deterministic function of the
        # shared state, so every round's expected mean is computable
        local_fns = {node: (lambda s, g=grads[node]: g - 0.1 * s)
                     for node in range(1, n + 1)}

        def apply_fn(state, avg):
            return state + avg

        async def go():
            broker = SafeBroker(progress_timeout=0.4, monitor_interval=0.1,
                                aggregation_timeout=30.0)
            addr = await broker.start()
            try:
                # reference: key derivations ONE round costs
                d0 = machines.key_derivations()
                await run_federated_rounds_net(
                    np.zeros(P, np.float32), local_fns, apply_fn, addr,
                    rounds=1, chunk_words=16)
                d_single = machines.key_derivations() - d0
                d1 = machines.key_derivations()
                state, results = await run_federated_rounds_net(
                    np.zeros(P, np.float32), local_fns, apply_fn, addr,
                    rounds=R, chunk_words=16,
                    failed_by_round={2: [3]})
                d_multi = machines.key_derivations() - d1
                return state, results, d_single, d_multi
            finally:
                await broker.stop()

        state, results, d_single, d_multi = asyncio.run(go())
        assert len(results) == R
        # expected evolution, recomputed in the clear
        exp = np.zeros(P, np.float32)
        for r in range(R):
            live = [nd for nd in range(1, n + 1) if not (r == 2 and nd == 3)]
            deltas = np.stack([grads[nd] - 0.1 * exp for nd in live])
            avg = np.asarray(results[r].average)
            np.testing.assert_allclose(avg, deltas.mean(0), atol=2e-3)
            exp = exp + avg  # apply the PUBLISHED average (bit-exact path)
        np.testing.assert_array_equal(state, exp)
        # round 2 ran 4(n-1)+2 messages (one dead org), others 4n
        for r, res in enumerate(results):
            expect = 4 * (n - 1) + 2 if r == 2 else 4 * n
            assert res.stats["aggregation_total"] == expect, (r, res.stats)
        # R rounds derive exactly what ONE round derives, plus the two
        # genuinely NEW pair keys of round 2's §5.3 repost (poster 2 and
        # receiver 4 each derive the never-before-used 2→4 hop pad) —
        # nothing already derived in Round 0 is ever derived again
        assert d_single > 0
        assert d_multi == d_single + 2


class TestCrossRoundPipelining:
    """§11 cross-round pipelining (ISSUE 9 tentpole): transfers and
    chunk relay namespaced by (session, round). The broker accepts —
    and relays — round r+1's chunk streams while round r's tail drains,
    parks round-tagged logical ops until ``advance_round`` opens the
    round, and delivers deferred transfers at the boundary, so the
    per-round MessageStats deltas keep the §5 closed forms and every
    round stays bit-identical to its sim twin."""

    def test_future_round_chunks_accepted_before_current_publishes(self):
        """THE §11 acceptance property, raw frames: a round-1 chunk is
        accepted and downloadable while round 0 is still incomplete
        (nothing published, nothing posted), with the logical op
        deferred to advance_round; stale-round stragglers are shed, and
        frames past the in-flight window get the busy backoff."""
        from repro.net import WireClient

        arr = np.arange(48, dtype=np.uint32)
        cw = 16  # 3 chunks

        def frame(seq, rnd):
            return {"session": 0, "op": "post_aggregate", "xfer": 5,
                    "seq": seq, "total": 3, "chunk_words": cw,
                    "from_node": 1, "to_node": 2, "group": 0,
                    "round": rnd, "payload": arr[seq * cw:(seq + 1) * cw]}

        async def go():
            broker = SafeBroker()
            addr = await broker.start()
            try:
                c = await WireClient(*addr).connect()
                await c.request("create_session", {"groups": {0: [1, 2]}})
                # round 0 is open and has seen NOTHING — post a chunk
                # addressed to round 1
                r = await c.request("post_chunk", frame(0, rnd=1))
                assert r["received"] == 1 and not r.get("superseded")
                assert r.get("status") != "busy"
                # the round-1 chunk is downloadable NOW: store-and-
                # forward relay across the round boundary
                got = await c.request("get_chunk", {
                    "session": 0, "kind": "get_aggregate", "node": 2,
                    "group": 0, "round": 1, "seq": 0, "words": cw,
                    "timeout": 5.0})
                assert np.array_equal(got["payload"], arr[:cw])
                # ...while round 0 remains untouched: no logical op, no
                # average, round counter still 0
                st = await c.request("get_stats", {"session": 0})
                assert st["round"] == 0
                assert st["post_aggregate"] == 0
                assert st["chunk_frames_future"] == 1
                assert (await c.request("peek_average",
                                        {"session": 0})) is None
                # completing the round-1 transfer STILL defers the op
                await c.request("post_chunk", frame(1, rnd=1))
                r = await c.request("post_chunk", frame(2, rnd=1))
                assert r["complete"]
                st = await c.request("get_stats", {"session": 0})
                assert st["post_aggregate"] == 0
                # advance_round opens round 1 and delivers the transfer
                adv = await c.request("advance_round", {"session": 0})
                assert adv["round"] == 1
                st = await c.request("get_stats", {"session": 0})
                assert st["round"] == 1
                assert st["post_aggregate"] == 1
                got = await c.request("get_aggregate", {
                    "session": 0, "node": 2, "group": 0, "round": 1,
                    "timeout": 5.0})
                assert np.array_equal(got["aggregate"], arr)
                # a straggler frame for the CLOSED round 0 is shed
                r = await c.request("post_chunk", frame(0, rnd=0))
                assert r.get("superseded") and r.get("stale_round")
                # a frame past the window (rounds {1, 2} in flight) is
                # refused with the §13 busy backoff, never buffered —
                # raw send/recv because WireClient.request would honour
                # the backoff and retry forever
                await c._send("post_chunk", frame(0, rnd=3))
                r = await c._recv("post_chunk")
                assert r.get("status") == "busy"
                st = await c.request("get_stats", {"session": 0})
                assert st["busy_rejections"] == 1
                await c.close()
            finally:
                await broker.stop()

        asyncio.run(go())

    def test_pipelined_rounds_bit_identical_closed_forms(self):
        """R rounds with window 2 on one session, streaming combine on:
        every round bit-identical to its independent sim twin, per-round
        4n closed form exact, and — the point — chunk frames of round
        r+1 observed on the broker while round r was still current."""
        from repro.net import PersistentNetSession

        n, V, R = 4, 103, 4
        rng = np.random.RandomState(90)
        rounds = [rng.uniform(-1, 1, (n, V)).astype(np.float32)
                  for _ in range(R)]

        async def go():
            broker = SafeBroker(progress_timeout=0.4, monitor_interval=0.1,
                                aggregation_timeout=30.0)
            addr = await broker.start()
            try:
                sess = PersistentNetSession(addr, n, chunk_words=16,
                                            stream=True)
                await sess.open()
                try:
                    out = await sess.run_rounds_pipelined(rounds)
                    raw = await sess._admin.request(
                        "get_stats", {"session": sess.sid})
                finally:
                    await sess.close()
                return out, raw
            finally:
                await broker.stop()

        out, raw = asyncio.run(go())
        assert len(out) == R
        for r, res in enumerate(out):
            sim = run_safe_round(rounds[r], counter=r * V)
            assert np.array_equal(sim.average, res.average), f"round {r}"
            assert res.stats["aggregation_total"] == 4 * n, (r, res.stats)
            assert res.initiator_elections == 0
            assert res.monitor_reposts == 0
        # cross-round overlap actually happened on the wire: the broker
        # accepted round r+1 chunk frames while round r was current
        assert raw["chunk_frames_future"] > 0
        assert raw["round"] == R

    def test_pipelined_unchunked_parks_and_stays_exact(self):
        """No chunk plane at all (V below every threshold): round r+1's
        ops simply park at the broker until the boundary — zero overlap,
        identical correctness. The degenerate end of §11."""
        from repro.net import PersistentNetSession

        n, V, R = 4, 16, 3
        rng = np.random.RandomState(91)
        rounds = [rng.uniform(-1, 1, (n, V)).astype(np.float32)
                  for _ in range(R)]

        async def go():
            broker = SafeBroker(progress_timeout=0.4, monitor_interval=0.1,
                                aggregation_timeout=30.0)
            addr = await broker.start()
            try:
                async with PersistentNetSession(addr, n) as sess:
                    return await sess.run_rounds_pipelined(rounds)
            finally:
                await broker.stop()

        out = asyncio.run(go())
        for r, res in enumerate(out):
            sim = run_safe_round(rounds[r], counter=r * V)
            assert np.array_equal(sim.average, res.average), f"round {r}"
            assert res.stats["aggregation_total"] == 4 * n, (r, res.stats)

    def test_federated_pipeline_staleness_one(self):
        """run_federated_rounds_net(pipeline=True): with window 2,
        round r's deltas are computed from the state through round r−2
        (staleness-1 pipelined FL). The whole evolution is recomputed in
        the clear and must match — including the exact fold of the
        published (bit-exact) averages into the final state."""
        from repro.net import run_federated_rounds_net

        n, P, R = 4, 103, 4
        rng = np.random.RandomState(43)
        grads = {node: rng.uniform(-1, 1, P).astype(np.float32)
                 for node in range(1, n + 1)}
        local_fns = {node: (lambda s, g=grads[node]: g - 0.1 * s)
                     for node in range(1, n + 1)}

        def apply_fn(state, avg):
            return state + avg

        async def go():
            broker = SafeBroker(progress_timeout=0.4, monitor_interval=0.1,
                                aggregation_timeout=30.0)
            addr = await broker.start()
            try:
                return await run_federated_rounds_net(
                    np.zeros(P, np.float32), local_fns, apply_fn, addr,
                    rounds=R, chunk_words=16, pipeline=True)
            finally:
                await broker.stop()

        state, results = asyncio.run(go())
        assert len(results) == R
        # the launch/collect schedule of window 2: rounds 0 and 1 launch
        # from the initial state; round r>=2 launches after round r-2
        # folded — so round r's deltas use the state through round r-2
        folded = np.zeros(P, np.float32)
        exp_states = [np.zeros(P, np.float32)]
        for r in range(R):
            used = exp_states[max(0, r - 1)]
            deltas = np.stack([grads[nd] - 0.1 * used
                               for nd in range(1, n + 1)])
            avg = np.asarray(results[r].average)
            np.testing.assert_allclose(avg, deltas.mean(0), atol=2e-3)
            folded = folded + avg  # the PUBLISHED average, bit-exact
            exp_states.append(folded.copy())
        np.testing.assert_array_equal(state, folded)
        for r, res in enumerate(results):
            assert res.stats["aggregation_total"] == 4 * n, (r, res.stats)


class TestAutoStreamThreshold:
    """ISSUE 6/9 small-n regression fix: ``stream=None`` (the default)
    skips the chunk plane wholesale below ``wire.MIN_STREAM_WORDS``,
    where per-chunk round-trips and the get_chunk/consume handshake
    dominate and there is nothing to overlap — a payload that small
    rides one frame anyway. Either path is bit-identical."""

    def test_small_payload_auto_skips_chunk_plane(self):
        from repro.net import wire

        V = 103
        assert V < wire.MIN_STREAM_WORDS
        vals = _vals(4, V, seed=60)
        net = _wire_round(vals, chunk_words=16)  # stream unspecified
        assert net.streamed_combines == 0
        # not just buffered: zero chunk frames — the payload took the
        # single-frame plain ops (the ISSUE 9 small-n fast path)
        assert net.stats["chunk_frames_in"] == 0
        assert net.stats["chunk_frames_out"] == 0
        assert np.array_equal(run_safe_round(vals).average, net.average)

    def test_threshold_payload_auto_streams(self):
        from repro.net import wire

        V = wire.MIN_STREAM_WORDS  # exactly at the threshold: streams
        vals = _vals(4, V, seed=61)
        net = _wire_round(vals, chunk_words=4096)
        assert net.streamed_combines == 4 - 1
        assert np.array_equal(run_safe_round(vals).average, net.average)

    def test_force_flags_override_auto(self):
        from repro.net import wire

        vals = _vals(4, 103, seed=62)
        on = _wire_round(vals, chunk_words=16, stream=True)
        assert on.streamed_combines == 3  # forced despite tiny payload
        big = _vals(4, wire.MIN_STREAM_WORDS, seed=63)
        off = _wire_round(big, chunk_words=4096, stream=False)
        assert off.streamed_combines == 0  # disabled despite large


class TestShardRouting:
    """ISSUE 6 sharded broker: sessions consistently hashed to worker
    processes by session id (``shard_of``), misdirected ops answered
    with the §12 redirect, rounds bit-identical to the sim through
    every entry path (shared SO_REUSEPORT port, direct ports, the
    dispatcher fallback)."""

    BROKER_KW = dict(progress_timeout=0.4, monitor_interval=0.1,
                     aggregation_timeout=30.0)

    def test_shard_hash_stable_across_processes(self):
        """The routing table is a pure function of the session id: a
        fresh interpreter computes the identical mapping (workers never
        exchange routing state — this IS the consistency guarantee)."""
        import json
        import os
        import subprocess
        import sys

        from repro.net import shard_of

        local = [shard_of(s, 4) for s in range(64)]
        code = ("import json; from repro.net.shard import shard_of; "
                "print(json.dumps([shard_of(s, 4) for s in range(64)]))")
        env = dict(os.environ)
        src = os.path.join(os.path.dirname(__file__), "..", "src")
        env["PYTHONPATH"] = os.path.abspath(src)
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             capture_output=True, text=True, timeout=60)
        assert out.returncode == 0, out.stderr
        assert json.loads(out.stdout) == local
        # owner allocation invariant: sid % shards == allocating shard
        assert all(shard_of(s, 4) == s % 4 for s in range(64))

    def test_sessions_pinned_to_one_shard(self):
        """Every op of a session is served by the worker that allocated
        it: the owner answers on its direct port, every OTHER worker
        answers the same op with a redirect naming the owner (it holds
        no state for the session)."""
        from repro.net import ShardedBroker, WireClient, shard_of

        async def go():
            sb = ShardedBroker(3, **self.BROKER_KW)
            addr = await sb.start()
            try:
                for k in range(3):
                    c = await WireClient(
                        addr[0], sb.shard_ports[k]).connect()
                    try:
                        created = await c.request("create_session", {
                            "groups": {0: [1, 2, 3]},
                            "aggregation_timeout": 5.0})
                        sid = created["session"]
                        # the allocator owns what it allocated, and
                        # advertises itself in the response
                        assert shard_of(sid, 3) == k
                        assert created["shard"] == k
                        assert created["port"] == sb.shard_ports[k]
                        for other in range(3):
                            if other == k:
                                continue
                            c2 = await WireClient(
                                addr[0], sb.shard_ports[other]).connect()
                            try:
                                # raw send/recv: observe the redirect
                                # itself (request() would follow it)
                                await c2._send("get_stats",
                                               {"session": sid})
                                res = await c2._recv("get_stats")
                                assert res["status"] == "redirect"
                                assert res["shard"] == k
                                assert res["port"] == sb.shard_ports[k]
                            finally:
                                await c2.close()
                        st = await c.request("get_stats", {"session": sid})
                        assert st["aggregation_total"] == 0
                        await c.request("delete_session", {"session": sid})
                    finally:
                        await c.close()
            finally:
                await sb.stop()

        asyncio.run(go())

    def test_wrong_shard_dial_completes_round_bit_identically(self):
        """Every learner dials the WRONG worker's direct port; the §12
        redirect settles each onto the owner after one bounce and the
        round completes — same bits as the sim, same §5 closed form."""
        from repro.core.machines import build_round_machines
        from repro.net import ShardedBroker, WireClient, shard_of
        from repro.net.client import drive_learner
        from repro.topology import RingTopology

        n, V = 4, 16
        vals = _vals(n, V, seed=50)

        async def go():
            sb = ShardedBroker(2, **self.BROKER_KW)
            addr = await sb.start()
            try:
                topo = RingTopology(n, 1)
                groups = topo.group_chains(node_base=1)
                initiators = {r + 1 for r in topo.elect_initiators()}
                machines = build_round_machines(
                    vals, topo, groups, initiators)
                admin = await WireClient(
                    addr[0], sb.shard_ports[0]).connect()
                try:
                    created = await admin.request("create_session", {
                        "groups": groups, "aggregation_timeout": 30.0})
                    sid = created["session"]
                    owner = shard_of(sid, 2)
                    wrong = sb.shard_ports[1 - owner]

                    async def drive(node, gen):
                        c = await WireClient(
                            addr[0], wrong, node=node,
                            token=admin.node_tokens[node]).connect()
                        try:
                            await drive_learner(
                                gen, c, sid,
                                aggregation_timeout=created[
                                    "aggregation_timeout"])
                            # the redirect moved this client's socket to
                            # the owning worker's direct port
                            assert c.port == sb.shard_ports[owner]
                        finally:
                            await c.close()

                    await asyncio.gather(
                        *(drive(nd, gen)
                          for nd, gen in machines.items()))
                    stats = await admin.request(
                        "get_stats", {"session": sid})
                    final = await admin.request(
                        "peek_average", {"session": sid})
                    await admin.request(
                        "delete_session", {"session": sid})
                    return stats, final
                finally:
                    await admin.close()
            finally:
                await sb.stop()

        stats, final = asyncio.run(go())
        sim = run_safe_round(vals)
        assert np.array_equal(sim.average, final["average"])
        assert stats["aggregation_total"] == 4 * n

    def test_rounds_via_shared_port_and_dispatcher(self):
        """Full rounds through both shared-port flavours: SO_REUSEPORT
        (kernel spreads first contacts) and the accept-and-hand-off
        dispatcher (``use_reuseport=False``) — bit-identical, closed
        form intact, and consecutive rounds land on distinct shards
        (the sid stride walks the workers)."""
        from repro.net import ShardedBroker

        vals = _vals(6, 16, seed=51)
        sim = run_safe_round(vals)

        async def go(use_reuseport):
            sb = ShardedBroker(2, use_reuseport=use_reuseport,
                               **self.BROKER_KW)
            addr = await sb.start()
            try:
                return [await run_safe_round_net(vals, addr)
                        for _ in range(2)]
            finally:
                await sb.stop()

        for use_reuseport in (True, False):
            for net in asyncio.run(go(use_reuseport)):
                assert np.array_equal(sim.average, net.average)
                assert net.stats["aggregation_total"] == 4 * 6

    def test_get_shard_map_single_broker(self):
        """The op is additive (§9): an UNsharded broker answers it too,
        reporting a one-shard world — clients need no capability probe."""

        async def go():
            broker = SafeBroker(**self.BROKER_KW)
            addr = await broker.start()
            try:
                from repro.net import WireClient

                c = await WireClient(*addr).connect()
                try:
                    return await c.request("get_shard_map", {})
                finally:
                    await c.close()
            finally:
                await broker.stop()

        m = asyncio.run(go())
        assert m == {"shards": 1, "shard": 0, "ports": [],
                     "shard_alive": [True]}


class _FakeEngineSession:
    def __init__(self, sid, values, rounds):
        self.sid = sid
        self.values = values
        self.rounds = rounds
        self.results = []
        self.rounds_done = 0

    @property
    def done(self):
        return self.rounds_done >= self.rounds


class _FakeEngine:
    """In-process numpy stand-in for serve.AggregationEngine exposing
    exactly the surface the broker drives (submit/step/queue/active/
    on_complete, n, V) — lets the engine-plane chunk routing be tested
    without jax or a device mesh."""

    def __init__(self, n, V):
        self.n, self.V = n, V
        self.queue = []
        self._sids = iter(range(1 << 30))
        self.on_complete = None

    @property
    def active(self):
        return 0

    def submit(self, values, *, rounds=1, **kw):
        if values.shape != (self.n, self.V):
            raise ValueError(f"values shape {values.shape} != "
                             f"({self.n}, {self.V})")
        sess = _FakeEngineSession(next(self._sids), values, rounds)
        self.queue.append(sess)
        return sess

    def step(self):
        if not self.queue:
            return 0
        sess = self.queue.pop(0)
        while not sess.done:
            sess.results.append(sess.values.mean(0))
            sess.rounds_done += 1
        if self.on_complete is not None:
            self.on_complete(sess)
        return 1


class TestEngineChunked:
    """ISSUE 4 satellite: oversized engine payloads route over the §6
    chunk plane instead of being refused at submit time."""

    def test_chunked_submit_and_wait_roundtrip(self):
        async def go():
            n, V = 4, 1000
            broker = SafeBroker(engine=_FakeEngine(n, V))
            addr = await broker.start()
            try:
                from repro.net import WireClient

                c = await WireClient(*addr).connect()
                vals = _vals(n, V, seed=50)
                sub = await c.submit_session_chunked(
                    {"values": vals, "rounds": 3}, chunk_words=256)
                res = await c.wait_session_chunked(
                    sub["sid"], timeout=30.0, chunk_words=512)
                assert res["status"] == "done" and res["rounds"] == 3
                for r in res["results"]:
                    assert np.array_equal(r, vals.mean(0))
                # idempotent re-fetch until the TTL prune
                again = await c.wait_session_chunked(
                    sub["sid"], timeout=5.0, chunk_words=512)
                assert again["status"] == "done"
                assert np.array_equal(again["results"][0], vals.mean(0))
                assert broker.engine_chunk_frames_in > 0
                assert broker.engine_chunk_frames_out > 0
                await c.close()
            finally:
                await broker.stop()

        asyncio.run(go())

    def test_oversized_plain_wait_refused_with_guidance(self, monkeypatch):
        """A result set beyond one frame is no longer refused at submit:
        submission succeeds, the UNCHUNKED wait errors with a pointer to
        the chunked fetch, and the chunked fetch delivers."""
        from repro.net import WireClient, wire as _w

        async def go():
            n, V = 4, 1000
            broker = SafeBroker(engine=_FakeEngine(n, V))
            addr = await broker.start()
            try:
                c = await WireClient(*addr).connect()
                vals = _vals(n, V, seed=51)
                # rounds*V*4 = 80 KB > the shrunken 64 KiB frame cap
                monkeypatch.setattr(_w, "MAX_FRAME", 1 << 16)
                sub = await c.request("submit_session",
                                      {"values": vals, "rounds": 20})
                with pytest.raises(_w.WireError, match="chunked"):
                    await c.request("wait_session",
                                    {"sid": sub["sid"], "timeout": 30.0})
                res = await c.wait_session_chunked(
                    sub["sid"], timeout=30.0, chunk_words=1024)
                assert res["status"] == "done" and res["rounds"] == 20
                assert np.array_equal(res["results"][19], vals.mean(0))
                await c.close()
            finally:
                await broker.stop()

        asyncio.run(go())

    def test_chunked_submit_repeat_final_chunk_idempotent(self):
        """A re-sent final submit chunk re-acks the SAME sid — never a
        second engine session (PROTOCOL.md §6 repeat rule, engine
        flavour)."""
        from repro.net import WireClient

        async def go():
            n, V = 2, 64
            eng = _FakeEngine(n, V)
            broker = SafeBroker(engine=eng)
            addr = await broker.start()
            try:
                c = await WireClient(*addr).connect()
                vals = _vals(n, V, seed=52)
                flat = vals.ravel()

                def frame(seq):
                    return {"op": "submit_session", "node": 9, "xfer": 3,
                            "seq": seq, "total": 2, "chunk_words": 64,
                            "rounds": 1,
                            "payload": flat[seq * 64:(seq + 1) * 64]}

                r0 = await c.request("post_chunk", frame(0))
                assert not r0["complete"]
                r1 = await c.request("post_chunk", frame(1))
                assert r1["complete"]
                r1b = await c.request("post_chunk", frame(1))  # repeat
                assert r1b["complete"] and r1b["sid"] == r1["sid"]
                assert len(broker._engine_sessions) == 1
                await c.close()
            finally:
                await broker.stop()

        asyncio.run(go())


ENGINE_WIRE_CODE = """
import asyncio, numpy as np, jax
from repro.core.types import ChainConfig
from repro.serve import AggregationEngine
from repro.net import SafeBroker, WireClient
from repro.launch.mesh import make_mesh

mesh = make_mesh((8,), ("data",))
n, V, S = 8, 32, 4
cfg = ChainConfig(num_learners=n, mode="safe")
engine = AggregationEngine(mesh, cfg, slots=S, payload_words=V)
rng = np.random.RandomState(0)

async def go():
    broker = SafeBroker(engine=engine)
    addr = await broker.start()
    try:
        clients = [await WireClient(*addr, node=t).connect()
                   for t in range(S)]
        tenant_vals = [rng.uniform(-1, 1, (n, V)).astype(np.float32)
                       for _ in range(S)]
        sids = []
        for t, c in enumerate(clients):
            sub = await c.request("submit_session", {
                "values": tenant_vals[t], "rounds": 2,
                "provisioning_seed": 0xC0FFEE + t,
                "learner_master": 0x5EED + t})
            sids.append(sub["sid"])
        for t, c in enumerate(clients):
            res = await c.request("wait_session",
                                  {"sid": sids[t], "timeout": 300.0})
            assert res["status"] == "done", res
            assert res["rounds"] == 2
            exp = tenant_vals[t].mean(0)
            for r in res["results"]:
                assert np.abs(r - exp).max() < 1e-3
        # wait_session is an idempotent read until the TTL prune: a
        # client whose first response was lost can re-fetch its results
        again = await clients[0].request("wait_session",
                                         {"sid": sids[0], "timeout": 1.0})
        assert again["status"] == "done" and again["rounds"] == 2
        # abandoned submissions (never waited on) are pruned after the
        # TTL instead of pinning their AggSession forever
        broker.engine_session_ttl = 0.0
        sub = await clients[0].request("submit_session", {
            "values": tenant_vals[0], "rounds": 1})
        abandoned = sub["sid"]
        # never waited on: the engine completes it, then the monitor's
        # TTL prune (ttl=0) must drop it without any further submits
        for _ in range(200):
            if (abandoned not in broker._engine_sessions
                    and abandoned not in broker._engine_done):
                break
            await asyncio.sleep(0.1)
        assert abandoned not in broker._engine_sessions, "abandoned session not pruned"
        broker.engine_session_ttl = 300.0  # new sessions must survive
        sub2 = await clients[0].request("submit_session", {
            "values": tenant_vals[0], "rounds": 1})
        res = await clients[0].request("wait_session",
                                       {"sid": sub2["sid"], "timeout": 300.0})
        assert res["status"] == "done"
        for c in clients:
            await c.close()
    finally:
        await broker.stop()

asyncio.run(go())
print("ENGINE_WIRE_OK")
"""


def test_engine_plane_over_wire():
    """S wire tenants batch through one AggregationEngine behind the
    broker (submit_session/wait_session), results correct per tenant."""
    out = run_multidevice(ENGINE_WIRE_CODE, devices=8)
    assert "ENGINE_WIRE_OK" in out


class TestObservability:
    """ISSUE 7: the metrics plane (PROTOCOL.md §13) — live snapshots,
    admission control, shard-death visibility, adaptive chunking and
    the deterministic backoff helper."""

    BROKER_KW = dict(progress_timeout=2.0, monitor_interval=0.5,
                     aggregation_timeout=30.0)

    def test_metrics_monotonic_and_uncounted(self):
        """Counters rise monotonically round over round; polling
        ``get_metrics`` mid-stream never perturbs the §5 closed form
        (admin-class: uncounted, untimed)."""
        from repro.net import PersistentNetSession, WireClient

        n, V = 4, 64
        vals = _vals(n, V, seed=70)

        async def go():
            broker = SafeBroker(**self.BROKER_KW)
            addr = await broker.start()
            try:
                mc = await WireClient(*addr).connect()
                sess = PersistentNetSession(addr, n, words_per_round=V)
                await sess.open()
                try:
                    m0 = await mc.request("get_metrics", {})
                    r1 = await sess.run_round(vals)
                    m1 = await mc.request("get_metrics", {})
                    for _ in range(5):  # free polls between rounds
                        await mc.request("get_metrics", {})
                    r2 = await sess.run_round(vals)
                    m2 = await mc.request("get_metrics",
                                          {"session": sess.sid})
                    return r1, r2, m0, m1, m2, sess.sid
                finally:
                    await sess.close()
                    await mc.close()
            finally:
                await broker.stop()

        r1, r2, m0, m1, m2, sid = asyncio.run(go())
        # snapshots are invisible to MessageStats: exact closed form
        assert r1.stats["aggregation_total"] == 4 * n
        assert r2.stats["aggregation_total"] == 4 * n
        assert np.array_equal(r1.average,
                              run_safe_round(vals).average)
        assert (m0["rounds_completed"], m1["rounds_completed"],
                m2["rounds_completed"]) == (0, 1, 2)
        hists = [m["series"]["histograms"]["safe_round_latency_seconds"]
                 for m in (m0, m1, m2)]
        assert [h["count"] for h in hists] == [0, 1, 2]
        assert 0.0 < m2["round_latency_p50_s"] <= m2["round_latency_p99_s"]
        for key in ("safe_rounds_completed_total",
                    "safe_chunk_frames_in_total"):
            series = [m["series"]["counters"][key] for m in (m0, m1, m2)]
            assert series == sorted(series), (key, series)
        # per-session view for the (still-open) tenant, narrowed by sid
        assert list(m2["sessions"]) == [sid]
        assert m2["sessions"][sid]["rounds_completed"] == 2
        assert m2["sessions"][sid]["chunk_backlog_bytes"] == 0
        assert m2["active_sessions"] == 1
        assert m2["rounds_per_s"] > 0

    def test_metrics_snapshot_schema(self):
        """The wire snapshot keeps a stable shape — dashboards and the
        SLO harness key into it."""
        from repro.net import WireClient

        async def go():
            broker = SafeBroker(**self.BROKER_KW)
            addr = await broker.start()
            try:
                await run_safe_round_net(_vals(4, 16, seed=71), addr)
                c = await WireClient(*addr).connect()
                try:
                    return await c.request("get_metrics", {})
                finally:
                    await c.close()
            finally:
                await broker.stop()

        m = asyncio.run(go())
        required = {
            "uptime_s", "shard", "shards", "rounds_completed",
            "rounds_per_s", "round_latency_p50_s", "round_latency_p99_s",
            "monitor_reposts", "initiator_elections", "busy_rejections",
            "redirects", "chunk_backlog_bytes", "active_sessions",
            "sessions", "series", "trace_spans"}
        assert required <= set(m), required - set(m)
        s = m["series"]
        assert set(s) == {"counters", "gauges", "histograms"}
        assert all(isinstance(v, int) for v in s["counters"].values())
        assert all(isinstance(v, float) for v in s["gauges"].values())
        h = s["histograms"]["safe_round_latency_seconds"]
        assert set(h) == {"count", "sum", "p50", "p99", "buckets"}
        # buckets are [bound, count] pairs ending at +Inf
        assert h["buckets"][-1][0] == float("inf")
        assert sum(b[1] for b in h["buckets"]) == h["count"] == 1
        # the transient round's session is gone again
        assert m["active_sessions"] == 0 and m["sessions"] == {}

    def test_flooding_tenant_busy_shed_bit_identical(self):
        """Admission control: a one-chunk budget forces the second
        parallel §5.5 group chain into busy/retry-after; the client's
        backoff loop replays it and the round still completes with the
        exact ``4n + g`` closed form, bit-identical to the sim."""
        n, V, chunk = 6, 2048, 128
        vals = _vals(n, V, seed=72)
        sim = run_safe_round(vals, subgroups=2)
        net = _wire_round(
            vals, subgroups=2, chunk_words=chunk, stream=False,
            broker_kw=dict(chunk_budget_bytes=chunk * 4,
                           progress_timeout=2.0, monitor_interval=0.5))
        assert np.array_equal(sim.average, net.average)
        assert net.stats["aggregation_total"] == 4 * n + 2
        assert net.stats["busy_rejections"] > 0

    def test_busy_never_triggers_with_ample_budget(self):
        """The default budget never sheds a well-behaved tenant — the
        steady-profile SLO baseline in miniature."""
        vals = _vals(6, 2048, seed=73)
        net = _wire_round(vals, subgroups=2, chunk_words=128, stream=False)
        assert net.stats["aggregation_total"] == 4 * 6 + 2
        assert net.stats["busy_rejections"] == 0

    def test_http_metrics_exporter(self):
        """GET /metrics answers Prometheus text; other paths 404."""

        async def go():
            broker = SafeBroker(**self.BROKER_KW)
            addr = await broker.start()
            haddr = await broker.start_metrics_http()
            try:
                await run_safe_round_net(_vals(4, 16, seed=74), addr)

                async def get(path):
                    r, w = await asyncio.open_connection(*haddr)
                    w.write(f"GET {path} HTTP/1.0\r\n\r\n".encode())
                    await w.drain()
                    body = (await r.read()).decode()
                    w.close()
                    return body

                ok = await get("/metrics")
                missing = await get("/nope")
                return ok, missing
            finally:
                await broker.stop()

        ok, missing = asyncio.run(go())
        assert ok.startswith("HTTP/1.0 200")
        assert 'safe_rounds_completed_total{shard="0"} 1' in ok
        assert 'safe_round_latency_seconds_bucket{shard="0",le="+Inf"} 1' in ok
        assert "# TYPE safe_round_latency_seconds histogram" in ok
        assert missing.startswith("HTTP/1.0 404")

    def test_shard_death_visible_and_survivors_serve(self):
        """Killing a worker marks it dead in ``get_shard_map``, fails
        its sessions fast with a clear error, and leaves sessionless
        traffic flowing to the survivors."""
        from repro.net import ShardedBroker, WireClient, wire as _w

        async def go():
            sb = ShardedBroker(2, use_reuseport=False, **self.BROKER_KW)
            addr = await sb.start()
            try:
                loop = asyncio.get_running_loop()
                sb._procs[0].terminate()
                await loop.run_in_executor(None, sb._procs[0].join, 10.0)
                assert not sb._procs[0].is_alive()
                c = await WireClient(*addr).connect()
                try:
                    m = await c.request("get_shard_map", {})
                    assert m["shard_alive"] == [False, True]
                    assert m["shard_deaths"] == 1
                    # session ops owned by the dead shard fail fast
                    # with a diagnosis, not a hang (sid 0 -> shard 0)
                    try:
                        await c.request("get_stats", {"session": 0})
                        raise AssertionError("expected WireError")
                    except _w.WireError as e:
                        assert "dead" in str(e)
                finally:
                    await c.close()
                # new sessions land on the live shard and run clean
                res = await run_safe_round_net(_vals(4, 8, seed=75), addr)
                assert res.stats["aggregation_total"] == 4 * 4
                assert sb.shard_deaths == 1
                return True
            finally:
                await sb.stop()

        assert asyncio.run(go())

    def test_backoff_delay_deterministic_and_capped(self):
        from repro.net import backoff_delay

        seq = [backoff_delay(a, base=0.02, seed=3) for a in range(12)]
        assert seq == [backoff_delay(a, base=0.02, seed=3)
                       for a in range(12)]  # replayable
        for a, d in enumerate(seq):
            hi = min(0.5, 0.02 * 2 ** a)
            assert 0.5 * hi <= d < hi  # jittered into [0.5, 1.0)*hi
        # capped, and huge attempt counts do not overflow the shift
        assert backoff_delay(10_000, base=0.02, seed=1) <= 0.5
        # co-tenants (different seeds) desynchronize
        assert any(backoff_delay(a, base=0.02, seed=1)
                   != backoff_delay(a, base=0.02, seed=2)
                   for a in range(4))

    def test_auto_chunk_words_quantized(self):
        from repro.net import auto_chunk_words, wire as _w

        for pw in (1, 1000, _w.MIN_STREAM_WORDS, 100_000, 1 << 20,
                   1 << 23, 1 << 26):
            aw = auto_chunk_words(pw)
            assert aw % _w.MIN_STREAM_WORDS == 0
            assert _w.MIN_STREAM_WORDS <= aw <= _w.DEFAULT_CHUNK_WORDS
        # small payloads come back whole (no chunk overhead)
        assert auto_chunk_words(1024) >= 1024
        # the legacy None path (> AUTO_CHUNK_WORDS) is preserved
        from repro.net.client import AUTO_CHUNK_WORDS, _resolve_chunk_words
        assert auto_chunk_words(1 << 26) == _w.DEFAULT_CHUNK_WORDS
        assert (_resolve_chunk_words(None, AUTO_CHUNK_WORDS + 1)
                == _w.DEFAULT_CHUNK_WORDS)
        assert _resolve_chunk_words(None, 64) is None
        assert _resolve_chunk_words(256, 1 << 26) == 256


class TestAuth:
    """PROTOCOL.md §15 session tokens: every sessioned op must present
    the opaque token minted at ``create_session``; denials come back as
    counted-neutral ``auth_failed`` responses (never in MessageStats,
    never timed), and ``reset_round`` rotates the whole grant."""

    BROKER_KW = dict(progress_timeout=0.4, monitor_interval=0.1,
                     aggregation_timeout=30.0)

    def test_tokenless_and_wrong_token_rejected(self):
        from repro.net import WireClient

        async def go():
            broker = SafeBroker(**self.BROKER_KW)
            addr = await broker.start()
            admin = await WireClient(*addr).connect()
            anon = await WireClient(*addr).connect()  # never gets a token
            try:
                grant = await admin.request(
                    "create_session", {"groups": {0: [1, 2, 3]}})
                sid = grant["session"]
                assert grant["token"]
                assert set(grant["node_tokens"]) == {1, 2, 3}
                assert len(set(grant["node_tokens"].values())) == 3

                # token-less op: rejected, with the op echoed back
                r_missing = await anon.request("get_stats",
                                               {"session": sid})
                # made-up token: rejected
                r_unknown = await anon.request(
                    "get_stats", {"session": sid, "token": "f" * 32})
                # node 1's token cannot act as node 2 (identity check)
                r_imperson = await anon.request(
                    "post_aggregate",
                    {"session": sid, "token": grant["node_tokens"][1],
                     "from_node": 2})
                # node tokens cannot run admin-only ops
                r_admin_op = await anon.request(
                    "reset_round",
                    {"session": sid, "token": grant["node_tokens"][1]})
                # ...while its own identity is fine at the auth layer
                # (short long-poll timeout: nothing is addressed to
                # node 2, the point is it gets PAST auth)
                r_self = await anon.request(
                    "check_aggregate",
                    {"session": sid, "token": grant["node_tokens"][2],
                     "node": 2, "timeout": 0.2})
                stats = await admin.request("get_stats", {"session": sid})
            finally:
                await anon.close()
                await admin.close()
                await broker.stop()
            return r_missing, r_unknown, r_imperson, r_admin_op, r_self, stats

        (r_missing, r_unknown, r_imperson, r_admin_op, r_self,
         stats) = asyncio.run(go())
        for r, why in ((r_missing, "missing"), (r_unknown, "unknown"),
                       (r_imperson, "node 1"), (r_admin_op, "admin")):
            assert r["status"] == "auth_failed", (why, r)
        assert r_missing["op"] == "get_stats"
        assert r_self.get("status") != "auth_failed", r_self
        # counted-neutral: four denials, zero protocol messages
        assert stats["auth_failures"] == 4
        assert stats["aggregation_total"] == 0

    def test_reset_round_rotates_tokens(self):
        """A captured token is worthless after ``reset_round``: the
        whole grant (admin + per-node) is re-minted, replaying the stale
        one is an ``auth_failed``, and the fresh grant works."""
        from repro.net import WireClient

        async def go():
            broker = SafeBroker(**self.BROKER_KW)
            addr = await broker.start()
            admin = await WireClient(*addr).connect()
            try:
                grant = await admin.request(
                    "create_session", {"groups": {0: [1, 2]}})
                sid = grant["session"]
                stale = grant["token"]
                stale_node = grant["node_tokens"][1]
                # WireClient adopts the rotated grant from the response
                grant2 = await admin.request("reset_round",
                                             {"session": sid})
                assert grant2["token"] != stale
                assert grant2["node_tokens"][1] != stale_node
                assert admin.token == grant2["token"]
                r_stale = await admin.request(
                    "get_stats", {"session": sid, "token": stale})
                r_stale_node = await admin.request(
                    "should_initiate",
                    {"session": sid, "token": stale_node, "node": 1})
                r_fresh = await admin.request("get_stats",
                                              {"session": sid})
            finally:
                await admin.close()
                await broker.stop()
            return r_stale, r_stale_node, r_fresh

        r_stale, r_stale_node, r_fresh = asyncio.run(go())
        assert r_stale["status"] == "auth_failed"
        assert r_stale_node["status"] == "auth_failed"
        assert r_fresh.get("status") != "auth_failed"
        assert r_fresh["auth_failures"] == 2

    def test_full_round_under_auth_is_unchanged(self):
        """The token plumbing is invisible to an honest round: same
        closed form, same bits as the sim (the §15 counted-neutral
        rule, asserted end-to-end)."""
        vals = _vals(4, 16, seed=91)
        sim = run_safe_round(vals)
        net = _wire_round(vals)
        assert np.array_equal(sim.average, net.average)
        assert net.stats["aggregation_total"] == 4 * 4
        assert net.stats["auth_failures"] == 0


class TestTLS:
    """Optional TLS on the broker listener (PROTOCOL.md §15): same
    protocol, same bits, over an encrypted transport."""

    def _certs(self, tmp_path):
        import shutil
        import subprocess

        openssl = shutil.which("openssl")
        if openssl is None:
            pytest.skip("openssl not available for self-signed certs")
        cert, key = tmp_path / "cert.pem", tmp_path / "key.pem"
        subprocess.run(
            [openssl, "req", "-x509", "-newkey", "rsa:2048",
             "-keyout", str(key), "-out", str(cert), "-days", "1",
             "-nodes", "-subj", "/CN=localhost"],
            check=True, capture_output=True)
        return str(cert), str(key)

    def test_tls_round_bit_identical(self, tmp_path):
        import ssl

        cert, key = self._certs(tmp_path)
        vals = _vals(4, 16, seed=92)

        async def go():
            broker = SafeBroker(progress_timeout=0.4, monitor_interval=0.1,
                                aggregation_timeout=30.0,
                                ssl_certfile=cert, ssl_keyfile=key)
            addr = await broker.start()
            ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_CLIENT)
            ctx.check_hostname = False
            ctx.verify_mode = ssl.CERT_NONE  # self-signed test cert
            try:
                return await run_safe_round_net(vals, addr, ssl=ctx)
            finally:
                await broker.stop()

        res = asyncio.run(go())
        sim = run_safe_round(vals)
        assert np.array_equal(sim.average, res.average)
        assert res.stats["aggregation_total"] == 4 * 4

    def test_plaintext_client_cannot_speak_to_tls_broker(self, tmp_path):
        from repro.net import ShardDeadError, WireClient, wire as _w

        cert, key = self._certs(tmp_path)

        async def go():
            broker = SafeBroker(progress_timeout=0.4, monitor_interval=0.1,
                                aggregation_timeout=30.0,
                                ssl_certfile=cert, ssl_keyfile=key)
            addr = await broker.start()
            try:
                c = await WireClient(*addr).connect()  # no ssl context
                try:
                    await asyncio.wait_for(
                        c.request("get_metrics", {}), timeout=5.0)
                    return None
                except (ShardDeadError, _w.WireError, ConnectionError,
                        OSError, asyncio.TimeoutError, EOFError) as e:
                    return e
                finally:
                    await c.close()
            finally:
                await broker.stop()

        assert asyncio.run(go()) is not None


class TestHierarchicalWire:
    """§5.10 chain-of-chains across two real brokers (parent + child
    host): per-level closed forms and sim↔wire bit-identity. The full
    fault matrix lives in tests/test_conformance.py."""

    def _round(self, vals, orgs, **kw):
        from repro.net import run_hierarchical_round_net

        parent_timeout = kw.pop("parent_timeout", 30.0)
        child_agg = kw.pop("aggregation_timeout", 30.0)

        async def go():
            parent = SafeBroker(aggregation_timeout=30.0,
                                progress_timeout=0.4, monitor_interval=0.1)
            child = SafeBroker(aggregation_timeout=child_agg,
                               progress_timeout=0.4, monitor_interval=0.1)
            paddr = await parent.start()
            caddr = await child.start()
            try:
                return await run_hierarchical_round_net(
                    vals, paddr, {g: caddr for g in range(orgs)},
                    aggregation_timeout=child_agg,
                    parent_timeout=parent_timeout, **kw)
            finally:
                await parent.stop()
                await child.stop()

        return asyncio.run(go())

    def test_clean_two_orgs_matches_sim_and_flat(self):
        from repro.core.protocol import run_hierarchical_round_sim

        vals = _vals(8, 16, seed=93)
        res = self._round(vals, 2)
        sim = run_hierarchical_round_sim(vals, orgs=2)
        flat = run_safe_round(vals, subgroups=2)
        for g in (0, 1):
            assert res.org_results[g].stats["aggregation_total"] == 4 * 4 + 1
            assert np.array_equal(res.org_averages[g],
                                  sim.org_averages[g])
        assert res.parent_stats["hierarchy_total"] == 2 * 2
        assert res.parent_stats["post_org_average"] == 2
        assert res.parent_stats["get_org_average"] == 2
        assert res.elided_orgs == ()
        assert np.array_equal(res.average, sim.average)
        assert np.array_equal(res.average, flat.average)

    def test_whole_org_elided_like_a_dead_learner(self):
        from repro.core.protocol import run_hierarchical_round_sim

        vals = _vals(8, 16, seed=94)
        res = self._round(vals, 2, failed_orgs=(1,), parent_timeout=1.5)
        sim = run_hierarchical_round_sim(vals, orgs=2, failed_orgs=(1,))
        assert res.elided_orgs == (1,)
        assert res.parent_stats["crashed_orgs"] == [1]
        assert res.parent_stats["hierarchy_total"] == 2 * 1
        assert np.array_equal(res.average, sim.average)
        # the surviving org ran its full chain untouched
        assert res.org_results[0].stats["aggregation_total"] == 4 * 4 + 1


class TestShardFailover:
    """§12 dead-shard recovery end-to-end: kill a worker mid-tenant,
    the stranded tenant sees a deterministic ``ShardDeadError``, and
    the replayed round (fresh session on a live shard, same seeds and
    counter) is bit-identical to the sim. The harness itself asserts
    the closed forms and bit-identity per round."""

    def test_kill_worker_mid_tenant_recovers_bit_identical(self):
        from repro.net import run_shard_failover_load

        row = asyncio.run(run_shard_failover_load(
            tenants=3, rounds_per_tenant=2, n=4, V=32, shards=2))
        # the dispatcher round-robins 3 sessions over 2 shards, so the
        # killed shard owned >= 1: the recovery path MUST have fired
        assert row["recoveries"] >= 1
        assert row["rounds_completed"] == 6
        assert row["killed_shard"] == 0
