#!/usr/bin/env python3
"""Memory plans of a train-step cell's programs for a described chip.

    JAX_PLATFORMS=cpu python bench/aot_plan.py \\
        --workload fed4-internlm2-1.8b-l4.safe_step --rows 1 2 4 8

Compiles ahead of time, for a TPU v5e 2x2 that is described and not
attached, the cell's SAFE train step (the driver's own
``make_train_step`` at the configuration's widths) at each number of rows
a learner, and the check's reference program (``refs/lm_step.py``, one
learner a chip) at the configuration's rows. Prints one JSON line per
program with the bytes a chip holds: arguments, outputs, temporaries,
aliased and their total. Nothing runs; no time is measured. The cell's
``rows_per_learner`` is the largest power of two whose step stays under
15 GB a chip.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def plan(compiled) -> dict:
    ma = compiled.memory_analysis()
    out = {"argument_bytes": int(ma.argument_size_in_bytes),
           "output_bytes": int(ma.output_size_in_bytes),
           "temp_bytes": int(ma.temp_size_in_bytes),
           "alias_bytes": int(ma.alias_size_in_bytes)}
    out["total_bytes"] = (out["argument_bytes"] + out["output_bytes"]
                          + out["temp_bytes"] - out["alias_bytes"])
    return out


def main(argv=None) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P

    import bench.run as run
    from repro.core import make_aggregator
    from repro.launch.mesh import make_mesh
    from repro.models import Model
    from repro.train.train_step import make_train_step

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rows", type=int, nargs="+", required=True)
    ap.add_argument("--topology", default="v5e:2x2")
    args = ap.parse_args(argv)

    found = run.resolve(args.workload)
    cfg, driver = found["config"], found["driver"]
    n, seq = cfg["learners"], cfg["seq_len"]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name=args.topology)
    mesh = make_mesh((n, 1), ("data", "model"), devices=topo.devices[:n])
    model = Model(driver.model_config(cfg))
    agg = make_aggregator(cfg["aggregator"], n, axis="data",
                          scale_bits=cfg["scale_bits"])
    b = make_train_step(model, agg, mesh, lr=cfg["lr"])
    sh = b.state_shardings
    rep = NamedSharding(mesh, P())

    def shaped(shape, dtype, sharding=rep):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    params = jax.tree.map(lambda x, s: shaped(x.shape, x.dtype, s),
                          b.params_abs, sh["params"])
    flat = shaped((b.padded_size,), jnp.float32, sh["master"])
    for rows in args.rows:
        tokens = shaped((n, rows, seq), jnp.int32,
                        NamedSharding(mesh, b.batch_spec))
        with jax.set_mesh(mesh):
            compiled = b.jit_fn.lower(
                params, flat, flat, flat, shaped((), jnp.int32, sh["fstep"]),
                shaped((), jnp.float32), shaped((), jnp.float32), tokens,
                shaped((1,), jnp.float32), shaped((n,), jnp.float32),
                shaped((), jnp.uint32), shaped((), jnp.uint32),
                shaped((n,), jnp.float32)).compile()
        print(json.dumps({"program": "train_step", "rows": rows,
                          **plan(compiled)}), flush=True)

    rows = cfg["rows_per_learner"]
    replicated = jax.tree.map(lambda x: shaped(x.shape, x.dtype),
                              b.params_abs)
    sizes = [int(np.prod(x.shape)) for x in jax.tree.leaves(b.params_abs)]
    per_leaf = found["workload"]["traffic"]["probe_words_per_leaf"]
    idx = [shaped((min(s, per_leaf),), jnp.int32) for s in sizes]
    tokens = shaped((n, rows, seq), jnp.int32, NamedSharding(mesh, P("data")))
    compiled = driver.reference(mesh, cfg).lower(replicated, tokens,
                                                 idx).compile()
    print(json.dumps({"program": "reference", "rows": rows,
                      **plan(compiled)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
