"""End-to-end behaviour tests for the full system.

The flagship check: a multi-step SAFE-secured training run on a real mesh
produces the same learning curve as insecure aggregation (the protocol is
semantically transparent), while the control-plane simulation of the same
round count shows the paper's message complexity.
"""
import numpy as np

import pytest

from helpers import run_multidevice
from repro.core.protocol import run_safe_round


def test_end_to_end_system():
    out = run_multidevice("""
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_smoke_config
from repro.models import Model
from repro.core import make_aggregator
from repro.data import make_federated_batches
from repro.train.train_step import make_train_step
from repro.serve.engine import ServeEngine, Request

# ---- train with SAFE over 4 learners × 2-way TP -------------------------
from repro.launch.mesh import make_mesh
mesh = make_mesh((4, 2), ("data", "model"))
cfg = get_smoke_config("internlm2-1.8b")
model = Model(cfg)
agg = make_aggregator("safe", 4, axis="data")
bundle = make_train_step(model, agg, mesh, lr=3e-3)
stream = make_federated_batches(cfg, 4, 2, 64, seed=0)
# small fixed dataset, multiple epochs (cross-org FL trains repeatedly
# over each org's local data)
batches = [jnp.asarray(stream.global_batch(i)["tokens"]) for i in range(2)]
state = bundle.init_state_fn(model.init(jax.random.key(0)))
losses = []
for step in range(8):
    state, m = bundle.step_fn(state, batches[step % 2],
                              agg.reserve_round(bundle.round_words))
    losses.append(float(m["loss"]))
assert losses[-1] < losses[0] - 0.5, f"insufficient learning: {losses}"

# ---- then serve the trained model ---------------------------------------
params = state["params"]
eng = ServeEngine(model, params, batch_slots=2, max_seq=64)
for i in range(3):
    eng.submit(Request(rid=i, prompt=np.arange(4 + i) % cfg.vocab, max_new=6))
eng.run_until_done()
assert eng.steps > 0
print("E2E_OK", losses[0], "->", losses[-1])
""", devices=8, timeout=1200)
    assert "E2E_OK" in out


def test_control_plane_matches_data_plane_average():
    """The message-level simulation and the device chain implement the
    same arithmetic: identical averages given identical inputs."""
    vals = np.random.RandomState(5).uniform(-1, 1, (4, 33)).astype(np.float32)
    sim = run_safe_round(vals, mode="safe").average
    out = run_multidevice("""
import jax, jax.numpy as jnp, numpy as np
from repro.core import make_aggregator
from repro.launch.mesh import make_mesh
mesh = make_mesh((4,), ("data",))
vals = jnp.asarray(np.random.RandomState(5).uniform(-1, 1, (4, 33))
                   .astype(np.float32))
agg = make_aggregator("safe", 4)
out = np.asarray(agg.aggregate_sharded(mesh, vals))
print("AVG", ",".join(f"{x:.6f}" for x in out))
""", devices=4)
    got = np.array([float(x) for x in
                    out.split("AVG ")[1].strip().split(",")])
    np.testing.assert_allclose(got, sim, atol=3e-4)
