"""Quickstart: SAFE-secured data-parallel training in ~40 lines.

Run (CPU, 8 host devices):
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
  PYTHONPATH=src python examples/quickstart.py
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp

from repro.configs import get_smoke_config
from repro.core import make_aggregator
from repro.data import make_federated_batches
from repro.launch.mesh import make_mesh
from repro.models import Model
from repro.train import MetricsLogger, make_train_step


def main():
    # 4 learners (cross-org chain) × 2-way tensor parallelism
    mesh = make_mesh((4, 2), ("data", "model"))
    cfg = get_smoke_config("internlm2-1.8b")
    model = Model(cfg)

    # the paper's technique: gradients flow through the SAFE chain instead
    # of an all-reduce — swap "safe" for "insec"/"saf"/"bon" to ablate
    aggregator = make_aggregator("safe", num_learners=4, axis="data")

    bundle = make_train_step(model, aggregator, mesh, lr=3e-3)
    state = bundle.init_state_fn(model.init(jax.random.key(0)))
    stream = make_federated_batches(cfg, num_learners=4, batch_per_learner=2,
                                    seq_len=128)
    # each org's local dataset: 4 batches, trained over multiple epochs
    dataset = [jnp.asarray(stream.global_batch(i)["tokens"])
               for i in range(4)]
    log = MetricsLogger(print_every=5)
    steps = 6 if os.environ.get("SAFE_SMOKE") else 30
    for step in range(steps):
        # fresh pads every step: (key epoch, counter base) from the
        # aggregator, which rotates the keys when an epoch's space runs out
        state, metrics = bundle.step_fn(
            state, dataset[step % len(dataset)],
            aggregator.reserve_round(bundle.round_words))
        log.log(step, loss=metrics["loss"], grad=metrics["grad_scale"])
    print("final loss:", float(metrics["loss"]))


if __name__ == "__main__":
    main()
