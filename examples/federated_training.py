"""Cross-organizational federated training over the SAFE wire plane.

The paper's actual use case, end to end in one script: an asyncio
broker (the controller "reduced to a mere message broker"), four
organizations with non-IID data and *different dataset sizes* each
running real local FedAvg steps (standalone jit — no device mesh
required), and their model deltas travelling the encrypted SAFE chain
over real TCP, chunk-streamed because a delta is bigger than one wire
frame (docs/PROTOCOL.md §6). Averaging is the paper's §5.6 weighted
mean, so no org reveals its dataset size. Midway, one organization
goes dark — the §5.3 failover path keeps training going on the
survivors.

The published delta here is bit-identical to the in-SPMD
`train/federated.py` round for the same seeds (tests/test_train.py).

Run:
  PYTHONPATH=src python examples/federated_training.py
(SAFE_SMOKE=1 shrinks the run for CI.)
"""
import asyncio
import os

import numpy as np

SMOKE = bool(os.environ.get("SAFE_SMOKE"))

import jax  # noqa: E402

from repro.configs import get_smoke_config  # noqa: E402
from repro.core.session import RoundCursor  # noqa: E402
from repro.data import make_federated_batches  # noqa: E402
from repro.models import Model  # noqa: E402
from repro.net import SafeBroker, run_federated_round_net  # noqa: E402
from repro.train import make_wire_federated  # noqa: E402

N_ORGS = 4
LOCAL_STEPS = 2
ROUNDS = 3 if SMOKE else 10
FAIL_AT = 2 if SMOKE else 5  # org #3 goes dark after this round
CHUNK_WORDS = 1 << 18  # stream deltas in 256k-word chunks


def main():
    cfg = get_smoke_config("internlm2-1.8b")
    model = Model(cfg)
    stream = make_federated_batches(cfg, N_ORGS, 2, 128)
    params = model.init(jax.random.key(0))

    # per-org dataset sizes (the §5.6 weights — never revealed)
    weights = np.array([4000.0, 1000.0, 2500.0, 500.0], np.float32)
    # each org's fixed private shard: LOCAL_STEPS microbatches per round
    org_tokens = {
        l + 1: np.stack([stream.learner_batch(l, k)["tokens"]
                         for k in range(LOCAL_STEPS)])
        for l in range(N_ORGS)}
    wf = make_wire_federated(model, org_tokens, local_steps=LOCAL_STEPS,
                             local_lr=2e-3)
    print(f"model delta: {wf.payload_words} words "
          f"({wf.payload_words * 4 / 1e6:.1f} MB/hop, "
          f"{-(-wf.payload_words // CHUNK_WORDS)} chunks)")

    # fresh counter space every round; refuses rather than wraps
    cursor = RoundCursor(wf.words_per_round(weighted=True))

    async def train(params):
        broker = SafeBroker(progress_timeout=0.5, monitor_interval=0.1,
                            aggregation_timeout=60.0)
        addr = await broker.start()
        try:
            for r in range(ROUNDS):
                failed = (3,) if r >= FAIL_AT else ()
                params, res = await run_federated_round_net(
                    params, wf.local_fns, wf.apply_fn, addr,
                    weights=weights, counter=cursor.next_round(),
                    failed_nodes=failed, chunk_words=CHUNK_WORDS)
                losses = [wf.last_losses[n] for n in sorted(wf.last_losses)
                          if n not in failed]
                tag = " (org 3 DOWN, failover active)" if failed else ""
                print(f"round {r:2d}: local_loss={np.mean(losses):.4f} "
                      f"delta={np.linalg.norm(res.average):.3f} "
                      f"msgs={res.stats['aggregation_total']} "
                      f"chunks={res.stats['chunk_frames_in']}"
                      f"/{res.stats['chunk_frames_out']}{tag}")
        finally:
            await broker.stop()
        return params

    asyncio.run(train(params))


if __name__ == "__main__":
    main()
