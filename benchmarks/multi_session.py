"""Multi-session engine throughput: rounds/sec vs. concurrent sessions.

Measures the batched AggregationEngine (S sessions through ONE compiled
shard_map program per step) against the unbatched loop (S separate
single-session aggregate calls) at S ∈ {1, 8, 32}, on an 8-host-device
mesh in a subprocess. The batched path amortizes program dispatch and
shares one ppermute schedule across sessions; the acceptance bar is
>2x rounds/sec at S=32.
"""
from __future__ import annotations

from benchmarks.common import (emit, run_device_subprocess, save_json,
                               standalone_bench)

_CODE = """
import json, time
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.core import ChainConfig, SecureAggregator
from repro.serve import AggregationEngine
from repro.launch.mesh import make_mesh

mesh = make_mesh((8,), ("data",))
n, V = 8, 4096
rng = np.random.RandomState(0)
cfg = ChainConfig(num_learners=n, mode="safe")

# ---- unbatched baseline: one jitted single-session program ------------
single = SecureAggregator(cfg)
def per_rank(v, ctr):
    return single.aggregate(v.reshape(-1), ctr)
shard_fn = jax.shard_map(per_rank, mesh=mesh, in_specs=(P("data"), P()),
                         out_specs=P(), axis_names=frozenset({"data"}),
                         check_vma=False)
single_fn = jax.jit(shard_fn)

def unbatched_rounds(vals_list, ctrs):
    outs = []
    with jax.set_mesh(mesh):
        for v, c in zip(vals_list, ctrs):
            outs.append(single_fn(v, c))
    return jax.block_until_ready(outs)

out = {}
for S in (1, 8, 32):
    vals = [jnp.asarray(rng.uniform(-1, 1, (n, V)).astype(np.float32))
            for _ in range(S)]
    ctrs = [jnp.asarray(np.uint32(s * V)) for s in range(S)]
    unbatched_rounds(vals, ctrs)  # compile + warm
    t0 = time.perf_counter()
    reps = 3
    for _ in range(reps):
        unbatched_rounds(vals, ctrs)
    t_un = (time.perf_counter() - t0) / reps

    eng = AggregationEngine(mesh, cfg, slots=S, payload_words=V)
    npvals = [np.asarray(v) for v in vals]
    for v in npvals:
        eng.submit(v)
    eng.step()  # compile + warm (one full round for every session)
    t0 = time.perf_counter()
    for _ in range(reps):
        for v in npvals:
            eng.submit(v)
        eng.step()
    t_b = (time.perf_counter() - t0) / reps

    out[str(S)] = {
        "sessions": S,
        "unbatched_wall_s": t_un,
        "batched_wall_s": t_b,
        "unbatched_rounds_per_s": S / t_un,
        "batched_rounds_per_s": S / t_b,
        "speedup": t_un / t_b,
    }
print("JSON" + json.dumps(out))
"""


def run() -> dict:
    payload = run_device_subprocess(_CODE)
    for S, row in payload.items():
        emit(f"multi_session/S{S}_batched", row["batched_wall_s"] * 1e6,
             f"rps={row['batched_rounds_per_s']:.1f} "
             f"speedup={row['speedup']:.2f}x")
        emit(f"multi_session/S{S}_unbatched", row["unbatched_wall_s"] * 1e6,
             f"rps={row['unbatched_rounds_per_s']:.1f}")
    save_json("multi_session", payload)
    return payload


def main():
    run()


if __name__ == "__main__":
    # standalone runs also emit BENCH_multi_session.json (stable
    # safe-bench/v1 schema), not just the legacy multi_session.json
    standalone_bench("multi_session", run)
