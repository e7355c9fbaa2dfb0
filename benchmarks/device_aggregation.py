"""Device data-plane benchmark: SAFE chain vs psum vs BON on a host mesh.

Runs in a subprocess on 8 virtual CPU devices (the bench process itself
stays single-device, and the child never takes a chip). Wall time on CPU
is not TPU-predictive — the *derived* columns (bytes over the learner
axis per aggregation, PRF work) are the roofline-relevant outputs; wall
time just sanity-checks the orderings.
"""
from __future__ import annotations

from benchmarks.common import emit, run_device_subprocess, save_json

_CODE = """
import json, time
import jax, jax.numpy as jnp, numpy as np
from repro.core import make_aggregator
from repro.launch.mesh import make_mesh

mesh = make_mesh((8,), ("data",))
n, V = 8, 1 << 20
vals = jnp.asarray(np.random.RandomState(0).uniform(-1, 1, (n, V))
                   .astype(np.float32))
out = {}
for name, agg in [
    ("insec", make_aggregator("insec", n)),
    ("safe_sequential", make_aggregator("safe", n)),
    ("safe_pipelined", make_aggregator("safe", n, pipelined=True)),
    ("safe_subgroups2", make_aggregator("safe", n, subgroups=2)),
    ("saf", make_aggregator("saf", n)),
    ("bon", make_aggregator("bon", n)),
]:
    r = agg.aggregate_sharded(mesh, vals)  # compile+run once
    jax.block_until_ready(r)
    ts = []
    for _ in range(3):
        t0 = time.perf_counter()
        jax.block_until_ready(agg.aggregate_sharded(mesh, vals))
        ts.append(time.perf_counter() - t0)
    # derived: bytes crossing the learner axis per aggregation (per link)
    hops = {"insec": 2, "saf": n, "safe_sequential": n,
            "safe_pipelined": 2, "safe_subgroups2": n // 2 + 1,
            "bon": 2}[name]
    out[name] = {"wall_s": sorted(ts)[1],
                 "axis_bytes_per_learner": hops * V * 4,
                 "prf_streams_per_learner":
                     {"insec": 0, "saf": 1, "safe_sequential": 3,
                      "safe_pipelined": 3, "safe_subgroups2": 3,
                      "bon": n + 1}[name]}
print("JSON" + json.dumps(out))
"""


def run() -> dict:
    payload = run_device_subprocess(_CODE, devices=8, timeout=1200)
    for name, row in payload.items():
        emit(f"device_agg/{name}", row["wall_s"] * 1e6,
             f"axis_MB={row['axis_bytes_per_learner']/2**20:.0f} "
             f"prf_streams={row['prf_streams_per_learner']}")
    save_json("device_aggregation", payload)
    return payload


def main():
    run()


if __name__ == "__main__":
    main()
