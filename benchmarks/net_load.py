"""Wire-plane load: S concurrent tenant sessions against one broker.

Three measurements over real localhost TCP:

  * engine plane — tenants submit whole sessions through
    ``submit_session``/``wait_session``; the broker batches them into
    one ``AggregationEngine`` compiled program per step (8-host-device
    subprocess, like the other mesh benchmarks). Reported: rounds/sec +
    p50/p99 submit→published latency at S ∈ {4, 16}.
  * protocol plane — each tenant runs full 8-learner SAFE rounds (one
    TCP connection per learner, 4n RPCs + long-polls per round)
    concurrently, at S ∈ {1, 4}; also once under a lossy/slow transport
    (latency + drop interceptors) to price fault handling.
  * scaling — the ISSUE 6 curve: protocol-plane rounds/s and p99 at
    S = 8 tenants against shards ∈ {1, 2, 4}
    (:class:`~repro.net.shard.ShardedBroker` worker processes behind
    one SO_REUSEPORT port), with the *client* side spread over worker
    processes too (``client_procs``) so the measured ceiling is the
    broker, not the load generator. ``host_cpus`` rides along in the
    payload: process sharding can only buy wall-clock where cores
    exist, so trajectory tooling must read the curve relative to it
    (a 1-core box measures ≈ flat — that is the honest number there).

``SAFE_SMOKE=1`` skips the jax engine subprocess and shrinks the
protocol/scaling shapes for CI. Rows land in the standard CSV/JSON
harness; `python -m benchmarks.run --bench-json` (or a standalone run)
also writes BENCH_net_load.json.
"""
from __future__ import annotations

import asyncio
import os

from benchmarks.common import (emit, run_device_subprocess, save_json,
                               standalone_bench)

SMOKE = bool(os.environ.get("SAFE_SMOKE"))
SHARD_COUNTS = (1, 2) if SMOKE else (1, 2, 4)
SCALE_TENANTS = 4 if SMOKE else 8
SCALE_ROUNDS = 2 if SMOKE else 4
SCALE_CLIENT_PROCS = max(SHARD_COUNTS)

_CODE = """
import asyncio, json, time
import numpy as np, jax
from repro.core.types import ChainConfig
from repro.serve import AggregationEngine
from repro.net import SafeBroker, LatencyInterceptor, DropInterceptor, Chain
from repro.net.loadgen import run_engine_load, run_protocol_load

out = {}

async def engine_plane():
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((8,), ("data",))
    n, V = 8, 1024
    for S in (4, 16):
        cfg = ChainConfig(num_learners=n, mode="safe")
        engine = AggregationEngine(mesh, cfg, slots=S, payload_words=V)
        broker = SafeBroker(engine=engine)
        addr = await broker.start()
        try:
            rep = await run_engine_load(addr, tenants=S,
                                        rounds_per_tenant=8, n=n, V=V)
        finally:
            await broker.stop()
        out[f"engine_S{S}"] = rep.row()

asyncio.run(engine_plane())
print("JSON" + json.dumps(out))
"""


async def _protocol_plane(out: dict) -> None:
    from repro.net import (Chain, DropInterceptor, LatencyInterceptor,
                           SafeBroker)
    from repro.net.loadgen import run_protocol_load

    broker_kw = dict(progress_timeout=0.5, monitor_interval=0.1,
                     aggregation_timeout=60.0)
    for S in (1, 4):
        broker = SafeBroker(**broker_kw)
        addr = await broker.start()
        try:
            rep = await run_protocol_load(addr, tenants=S,
                                          rounds_per_tenant=3, n=8, V=256)
        finally:
            await broker.stop()
        out[f"protocol_S{S}"] = rep.row()
    # lossy/slow transport: what §5.3-ready transport handling costs
    broker = SafeBroker(**broker_kw)
    addr = await broker.start()
    try:
        # factory form: per-tenant interceptors, reproducible fault plans
        ic = lambda t: Chain(  # noqa: E731
            LatencyInterceptor(mean=0.002, seed=1 + 2 * t),
            DropInterceptor(p=0.02, seed=2 + 2 * t))
        rep = await run_protocol_load(addr, tenants=2, rounds_per_tenant=2,
                                      n=8, V=256, interceptor=ic)
    finally:
        await broker.stop()
    out["protocol_S2_faulty"] = rep.row()


def _host_cpus() -> int:
    try:
        return len(os.sched_getaffinity(0))
    except (AttributeError, OSError):
        return os.cpu_count() or 1


async def _scaling(out: dict) -> None:
    """Rounds/s and p99 vs shard count at fixed tenant load; the client
    side uses the same ``client_procs`` for EVERY row so the only
    variable along the curve is the broker topology."""
    from repro.net import SafeBroker, ShardedBroker
    from repro.net.loadgen import run_protocol_load

    broker_kw = dict(progress_timeout=0.5, monitor_interval=0.1,
                     aggregation_timeout=60.0)
    scaling: dict = {
        "host_cpus": _host_cpus(),
        "tenants": SCALE_TENANTS,
        "rounds_per_tenant": SCALE_ROUNDS,
        "client_procs": SCALE_CLIENT_PROCS,
    }
    rps: dict = {}
    for shards in SHARD_COUNTS:
        if shards > 1:
            broker = ShardedBroker(shards, **broker_kw)
        else:
            broker = SafeBroker(**broker_kw)
        addr = await broker.start()
        try:
            # warm pass (connections, key derivation, spawn caches) then
            # best of two measured passes — localhost wall jitter
            await run_protocol_load(
                addr, tenants=SCALE_TENANTS, rounds_per_tenant=1,
                n=8, V=256, client_procs=SCALE_CLIENT_PROCS)
            reps = []
            for _ in range(2):
                reps.append(await run_protocol_load(
                    addr, tenants=SCALE_TENANTS,
                    rounds_per_tenant=SCALE_ROUNDS, n=8, V=256,
                    client_procs=SCALE_CLIENT_PROCS))
            rep = max(reps, key=lambda r: r.rounds_per_s)
        finally:
            await broker.stop()
        row = dict(rep.row(), shards=shards)
        scaling[f"shards{shards}"] = row
        rps[shards] = rep.rounds_per_s
        out[f"scaling_shards{shards}"] = row
    for shards in SHARD_COUNTS[1:]:
        scaling[f"speedup_{shards}x"] = rps[shards] / rps[1]
    out["scaling"] = scaling


def run() -> dict:
    out: dict = {}
    if SMOKE:
        out["engine_skipped"] = "SAFE_SMOKE"
    else:
        out.update(run_device_subprocess(_CODE))
    asyncio.run(_protocol_plane(out))
    asyncio.run(_scaling(out))
    for key, row in out.items():
        if not isinstance(row, dict) or "p50_s" not in row:
            continue
        extra = f" shards={row['shards']}" if "shards" in row else ""
        emit(f"net_load/{key}", row["p50_s"] * 1e6,
             f"rps={row['rounds_per_s']:.1f} "
             f"p99={row['p99_s']*1e3:.1f}ms tenants={row['tenants']}"
             f"{extra}")
    sc = out["scaling"]
    curve = " ".join(
        f"S{s}={sc[f'shards{s}']['rounds_per_s']:.1f}"
        for s in SHARD_COUNTS)
    emit("net_load/scaling", sc[f"shards{SHARD_COUNTS[-1]}"]["p99_s"] * 1e6,
         f"rounds/s {curve} cpus={sc['host_cpus']}")
    save_json("net_load", out)
    return out


def main():
    run()


if __name__ == "__main__":
    standalone_bench("net_load", run)
