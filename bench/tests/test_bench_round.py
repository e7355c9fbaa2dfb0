"""The round cell's check at a size a test run holds: a sound run is
correct; a run with its timed path broken is not; the control fails.

The whole run goes through the harness (``run.main``) past its look for
a chip, on the CPU with the kernels interpreted."""
import json

import jax
import numpy as np
import pytest

import bench.run as run
from bench.lib.cell import Context

CELL = "fed36-internvl2-1b.round"
LEARNERS = 6


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """The cell's own workload and traffic over a small configuration."""
    root = tmp_path_factory.mktemp("round")
    (root / "workloads").mkdir()
    (root / "configs").mkdir()
    found = run.resolve(CELL)
    cfg = dict(found["config"], learners=LEARNERS, alive=LEARNERS,
               update_words=3 * 8192 + 5)
    wl = dict(found["workload"], config="small")
    wl["traffic"] = dict(wl["traffic"], probe_words=1000)
    (root / "configs" / "small.json").write_text(json.dumps(cfg))
    (root / "workloads" / f"{CELL}.json").write_text(json.dumps(wl))
    return str(root)


def measure(root, capsys, seed=3_000_000_019):
    rc = run.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                   "0.2"], root=root, require_chip=False, compile_cache=False)
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_sound_run_is_correct(root, capsys):
    result = measure(root, capsys)
    assert result["correct"] and result["failed"] == 0
    assert result["checks"]["ring_mismatch_words"]["value"] == 0
    assert result["checks"]["cipher_mismatch_words"]["value"] == 0


def _pass_through(cipher, *args):
    return cipher


def _broken(fault, orig):
    def programs(*args, **kwargs):
        initiate, hop, unmask, probe = orig(*args, **kwargs)
        if fault == "hop_returns_its_state":
            hop = _pass_through
        elif fault == "half_left_out":
            full = hop

            def hop(cipher, x, keys, base, i):
                if int(i) >= LEARNERS // 2:
                    return cipher
                return full(cipher, x, keys, base, i)
        elif fault == "answer_altered":
            full_unmask = unmask

            def unmask(cipher, keys, base):
                return full_unmask(cipher, keys, base).at[7].add(1)
        return initiate, hop, unmask, probe
    return programs


@pytest.mark.parametrize("fault", ["hop_returns_its_state", "half_left_out",
                                   "answer_altered"])
def test_broken_timed_path_is_not_correct(root, capsys, monkeypatch, fault):
    driver = run.resolve(CELL, root=root)["driver"]
    monkeypatch.setattr(driver, "programs", _broken(fault, driver.programs))
    if fault == "half_left_out":
        # the published mean taken over the learners that were left in
        from repro.crypto.fixedpoint import FixedPointCodec
        mean = FixedPointCodec.decode_mean
        monkeypatch.setattr(FixedPointCodec, "decode_mean",
                            lambda self, u, n: mean(self, u, n // 2))
    result = measure(root, capsys)
    assert not result["correct"] and result["failed"] >= 1
    assert result["checks"]["ring_mismatch_words"]["value"] > 0


def test_control_is_not_correct(root):
    found = run.resolve(CELL, root=root)
    ctx = Context(CELL, found["config"], found["workload"]["traffic"], 0, 1,
                  jax.devices()[:1])
    limits = found["config"]["limits"]
    for reading in found["driver"].control(ctx, [11, 4_000_000_003]):
        assert reading["ring_mismatch_words"] > limits["ring_mismatch_words"]
        assert reading["mean_abs_err"] > limits["mean_abs_err"]
        assert np.isfinite(reading["mean_abs_err"])
