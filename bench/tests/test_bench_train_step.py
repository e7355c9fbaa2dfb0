"""The SAFE train-step cell: its configuration is the published model cut
in depth alone, and its check at a size a test run holds: a sound run is
correct; a run with a learner left out of the chain is not, nor one whose
step leaves the parameters, or the whole state, as they were.

The whole run goes through the harness (``run.main``) past its look for
a chip, on 4 virtual CPU devices in a child process."""
import dataclasses
import json
import os
import subprocess
import sys

import jax
import pytest

import bench.run as run

CELL = "fed4-internlm2-1.8b-l4.safe_step"
ROOT = os.path.dirname(run.BENCH_DIR)


def test_config_is_the_published_model_cut_to_four_layers():
    from repro.configs import get_config
    from repro.models import Model
    from repro.train.flatten import tree_size
    found = run.resolve(CELL)
    cfg = found["config"]
    published = get_config(cfg["arch"])
    assert cfg["reduced"] == ["num_hidden_layers"] and published.n_layers == 24
    mc = found["driver"].model_config(cfg)
    assert mc == dataclasses.replace(published, n_layers=4)
    size = tree_size(jax.eval_shape(Model(mc).init, jax.random.key(0)))
    assert size == cfg["update_words"] == 630_736_896


SMALL = dict(hidden_size=256, intermediate_size=768, num_attention_heads=4,
             num_key_value_heads=2, num_hidden_layers=2, vocab_size=512,
             seq_len=64, rows_per_learner=2, update_words=1_836_288)

CHILD = """
import sys
import jax.numpy as jnp
import bench.run as run
# the module the harness will load for the cell's driver
train_step = run.load_module(run.find({root!r}, "drivers", "train_step",
                                      ".py"), "bench_driver_train_step")
if {fault!r} == "learner_left_out":
    def _step(self, tokens):
        slot = self.agg.reserve_round(self.bundle.round_words)
        self.state, m = self.bundle.step_fn(
            self.state, tokens, slot, alive=jnp.asarray([1.0, 1.0, 1.0, 0.0]))
        return m
    train_step.SafeTrainStep._step = _step
if {fault!r} in ("params_not_updated", "state_left_unchanged"):
    import jax
    step = train_step.SafeTrainStep._step

    def _step(self, tokens):
        old = jax.tree.map(jnp.copy, self.state)
        m = step(self, tokens)
        if {fault!r} == "params_not_updated":
            self.state["params"] = old["params"]
        else:
            self.state = old
        return m
    train_step.SafeTrainStep._step = _step
sys.exit(run.main(["--workload", {cell!r}, "--seed", "3000000019",
                   "--seconds", "1"], root={root!r}, require_chip=False,
                  compile_cache=False))
"""


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """The cell's own workload and traffic over a small configuration."""
    root = tmp_path_factory.mktemp("train_step")
    (root / "workloads").mkdir()
    (root / "configs").mkdir()
    found = run.resolve(CELL)
    cfg = dict(found["config"], **SMALL)
    wl = dict(found["workload"], config="small")
    wl["traffic"] = dict(wl["traffic"], probe_words_per_leaf=4096)
    (root / "configs" / "small.json").write_text(json.dumps(cfg))
    (root / "workloads" / f"{CELL}.json").write_text(json.dumps(wl))
    return str(root)


def measure(root, fault=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([os.path.join(ROOT, "src"), ROOT]))
    proc = subprocess.run(
        [sys.executable, "-c", CHILD.format(fault=fault, cell=CELL,
                                            root=root)],
        capture_output=True, text=True, timeout=900, env=env, cwd=ROOT)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_sound_run_is_correct(root):
    result = measure(root)
    assert result["correct"] and result["failed"] == 0, result["checks"]
    assert set(result["metrics"]) == {"round_s", "setup_s"}
    assert result["device"]["count"] == 4


def test_a_learner_left_out_is_not_correct(root):
    result = measure(root, "learner_left_out")
    assert not result["correct"], result["checks"]
    checks = result["checks"]
    assert checks["mean_grad_err"]["value"] > checks["mean_grad_err"]["limit"]


@pytest.mark.parametrize("fault", ["params_not_updated",
                                   "state_left_unchanged"])
def test_a_state_left_unchanged_is_not_correct(root, fault):
    result = measure(root, fault)
    assert not result["correct"], result["checks"]
    update = result["checks"]["update_err"]
    assert update["value"] > update["limit"]
    assert update["value"] > 0.5  # unchanged words read 1
