"""chip_smoke.py on the CPU: it refuses to run without a TPU, and its
phases pass at tiny sizes (Pallas in interpret mode, the four-chip phase
on four virtual devices). The compile-cache helper it calls is checked
here too, in child processes, so the test process keeps its own config."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from helpers import REPO, run_multidevice

sys.path.insert(0, REPO)
import chip_smoke  # noqa: E402

SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(script, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, script], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def _has_result(stdout):
    lines = stdout.strip().splitlines()
    if not lines:
        return False
    try:
        return json.loads(lines[-1]).get("ok") is True
    except ValueError:
        return False


def test_exits_nonzero_without_tpu():
    proc = _run(SMOKE, REPO)
    assert proc.returncode != 0
    assert not _has_result(proc.stdout)
    assert "no TPU" in proc.stderr


def test_exits_nonzero_without_the_repo(tmp_path):
    lone = tmp_path / "chip_smoke.py"
    shutil.copy(SMOKE, lone)
    proc = _run(str(lone), str(tmp_path))
    assert proc.returncode != 0
    assert not _has_result(proc.stdout)


def test_one_chip_phase_tiny():
    r = chip_smoke.one_chip_phase(n=5, V=3000, seed=3, interpret=True,
                                  batched_sessions=2, batched_words=1000,
                                  bon_keys=3, bon_words=777)
    assert r["ring_mismatched_words"] == 0
    assert r["batched_mismatched_words"] == 0
    assert r["bon_mismatched_words"] == 0
    assert r["max_mean_err"] <= chip_smoke.MEAN_TOL
    assert r["V"] == r["V_full"] == 3000


def test_four_chip_phases_tiny():
    out = run_multidevice(f"""
import sys
sys.path.insert(0, {REPO!r})
import chip_smoke
from repro.configs import get_smoke_config
from repro.launch.mesh import make_mesh
r = chip_smoke.engine_phase(make_mesh((4,), ("data",)), V=1000, seed=1)
assert r["bit_exact"] and r["rounds"] == 2, r
r = chip_smoke.train_phase(make_mesh((4, 1), ("data", "model")),
                           get_smoke_config("internlm2-1.8b"), steps=3,
                           batch_per_learner=2, seq_len=32, lr=1e-3, seed=1)
assert r["max_loss_gap"] < chip_smoke.TRAIN_LOSS_TOL, r
print("FOUR_CHIP_PHASES_OK")
""", devices=4)
    assert "FOUR_CHIP_PHASES_OK" in out


@pytest.mark.parametrize("env_dir", [None, "/nonexistent/cache-from-env"])
def test_compile_cache_dir(env_dir):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(REPO, "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    code = ("import jax\n"
            "from repro.launch.compile_cache import enable_compile_cache\n"
            "print(enable_compile_cache())\n"
            "print(jax.config.jax_compilation_cache_dir)\n")
    out = subprocess.run([sys.executable, "-c", code], env=env, check=True,
                         capture_output=True, text=True,
                         timeout=120).stdout.split()
    want = env_dir or os.path.join(REPO, ".jax_cache")
    assert out == [want, want]
