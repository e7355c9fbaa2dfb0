"""Training launcher.

Examples:
  # small real run on 8 virtual CPU devices (the quickstart path)
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
  python -m repro.launch.train --arch internlm2-1.8b --smoke \\
      --steps 50 --learners 4 --model-shards 2 --aggregator safe

  # on a TPU v5e 2x2 host: one learner per chip
  python -m repro.launch.train --arch internlm2-1.8b --smoke \\
      --learners 4 --model-shards 1

  # federated (FedAvg, weighted SAFE delta aggregation)
  ... --federated --local-steps 4
"""
from __future__ import annotations

import argparse
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--batch-per-learner", type=int, default=2)
    ap.add_argument("--learners", type=int, default=4)
    ap.add_argument("--model-shards", type=int, default=2)
    ap.add_argument("--aggregator", default="safe",
                    choices=["safe", "saf", "insec", "bon"])
    ap.add_argument("--pipelined", action="store_true")
    ap.add_argument("--subgroups", type=int, default=1)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--federated", action="store_true")
    ap.add_argument("--local-steps", type=int, default=4)
    ap.add_argument("--fail-learners", default="",
                    help="comma-separated learner ranks to mark dead (failover demo)")
    ap.add_argument("--fail-at-step", type=int, default=-1)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--metrics", default="")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs import get_config, get_smoke_config
    from repro.core import make_aggregator
    from repro.data import make_federated_batches
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_mesh
    from repro.models import Model
    from repro.train import (MetricsLogger, make_federated_round,
                             make_train_step)
    from repro.ckpt import save_checkpoint, restore_checkpoint, latest_step

    enable_compile_cache()
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    model = Model(cfg)
    mesh = make_mesh((args.learners, args.model_shards), ("data", "model"))

    agg = make_aggregator(args.aggregator, args.learners, axis="data",
                          pipelined=args.pipelined, subgroups=args.subgroups,
                          weighted=args.federated)
    stream = make_federated_batches(cfg, args.learners,
                                    args.batch_per_learner, args.seq_len,
                                    seed=args.seed)
    log = MetricsLogger(args.metrics or None)
    params = model.init(jax.random.key(args.seed))
    dead = {int(x) for x in args.fail_learners.split(",") if x}

    t0 = time.time()
    if args.federated:
        bundle = make_federated_round(model, agg, mesh,
                                      local_steps=args.local_steps,
                                      local_lr=args.lr)
        for r in range(args.steps):
            toks = np.stack([
                np.stack([stream.learner_batch(l, r * args.local_steps + k)
                          ["tokens"] for k in range(args.local_steps)])
                for l in range(args.learners)])
            gb = stream.global_batch(r)
            alive = np.ones(args.learners, np.float32)
            if dead and (args.fail_at_step < 0 or r >= args.fail_at_step):
                alive[list(dead)] = 0.0
            slot = agg.reserve_round(bundle.round_words)
            params, m = bundle.round_fn(
                params, jnp.asarray(toks), slot,
                weights=jnp.asarray(gb["weights"]), alive=jnp.asarray(alive))
            log.log(r, key_epoch=slot.epoch, counter=slot.base,
                    **{k: float(v) for k, v in m.items()})
    else:
        bundle = make_train_step(model, agg, mesh, lr=args.lr)
        state = bundle.init_state_fn(params)
        start = 0
        if args.ckpt_dir and (s := latest_step(args.ckpt_dir)) is not None:
            state, extra = restore_checkpoint(args.ckpt_dir, s, state)
            start = int(extra.get("step", s))
            agg.resume(extra["key_epoch"], extra["counter_next"])
            print(f"resumed from step {start}")
        for step in range(start, args.steps):
            gb = stream.global_batch(step)
            alive = np.ones(args.learners, np.float32)
            if dead and (args.fail_at_step < 0 or step >= args.fail_at_step):
                alive[list(dead)] = 0.0
            slot = agg.reserve_round(bundle.round_words)
            state, m = bundle.step_fn(state, jnp.asarray(gb["tokens"]), slot,
                                      alive=jnp.asarray(alive))
            log.log(step, loss=float(m["loss"]),
                    grad_scale=float(m["grad_scale"]),
                    key_epoch=slot.epoch, counter=slot.base)
            if args.ckpt_dir and (step + 1) % args.ckpt_every == 0:
                # the key state after the steps up to the next checkpoint:
                # a resume starts past every pad this run can still use
                keys = agg.key_state_after(args.ckpt_every, bundle.round_words)
                save_checkpoint(args.ckpt_dir, step + 1, state,
                                extra={"step": step + 1, **keys})
    print(f"done in {time.time() - t0:.1f}s")


if __name__ == "__main__":
    main()
