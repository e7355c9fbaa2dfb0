"""The round cell's compiles, through the whole harness (``run.main``) at
a size a test run holds, on the CPU with the kernels interpreted: set-up
compiles every program the window runs, and the window compiles
nothing."""
import json
import time

import pytest

import bench.run as run
from bench.lib import compiles

CELL = "fed36-internvl2-1b.round"
LEARNERS = 4
COMPILES = "jax_compiles_total"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """The cell's own workload and traffic over a small configuration."""
    root = tmp_path_factory.mktemp("round_compiles")
    (root / "workloads").mkdir()
    (root / "configs").mkdir()
    found = run.resolve(CELL)
    cfg = dict(found["config"], learners=LEARNERS, alive=LEARNERS,
               update_words=2 * 8192 + 3)
    wl = dict(found["workload"], config="small")
    wl["traffic"] = dict(wl["traffic"], probe_words=500)
    (root / "configs" / "small.json").write_text(json.dumps(cfg))
    (root / "workloads" / f"{CELL}.json").write_text(json.dumps(wl))
    return str(root)


def test_the_window_compiles_nothing(root, capsys, monkeypatch):
    found = run.resolve(CELL, root=root)
    # loading the cell's readers started the count, before any set-up
    assert "compile_s.setup" in found["readers"]
    assert compiles.REGISTRY is not None
    seen = {}
    window = found["driver"].ChainRound.run_window

    def counted(cell, seconds):
        seen["start"] = compiles.REGISTRY.snapshot()
        start_s = time.time()
        win = window(cell, seconds)
        seen["end"] = compiles.REGISTRY.snapshot()
        # read where the traced harness reads it: after the window, before
        # the check compiles the reference
        seen["setup"] = compiles.setup_seconds(start_s)
        return win

    monkeypatch.setattr(found["driver"].ChainRound, "run_window", counted)
    rc = run.main(["--workload", CELL, "--seed", "4_000_000_037",
                   "--seconds", "0.3"], root=root, require_chip=False,
                  compile_cache=False)
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and result["correct"] and result["attempted"] >= 2
    start, end = seen["start"]["counters"], seen["end"]["counters"]
    assert start[COMPILES] > 0  # set-up compiled the round's programs
    assert end == start
    assert seen["setup"] is not None and seen["setup"] > 0
