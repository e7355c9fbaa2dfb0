"""The harness finds a cell's workload, configuration, driver and
metrics by name: a throwaway cell needs new files only."""
import json
import os
import textwrap

import bench.run as run

DRIVER = '''
import time
from bench.lib.cell import Check, Window

def setup(ctx):
    return Cell(ctx)

class Cell:
    def __init__(self, ctx):
        self.per_unit = ctx.traffic["unit_s"]
        self.counts = {"answer": ctx.config["answer"]}

    def run_window(self, seconds):
        start, done = time.perf_counter(), 0
        while time.perf_counter() - start < seconds:
            time.sleep(self.per_unit)
            done += 1
        end = time.perf_counter()
        return Window(start, end, done, {"unit_s": (end - start) / done})

    def release(self):
        pass

    def check(self):
        return [Check("answer_gap", abs(self.counts["answer"] - 42), 0)]
'''


def test_a_new_cell_is_found_by_name(tmp_path, capsys):
    for d in ("workloads", "configs", "drivers"):
        (tmp_path / d).mkdir()
    (tmp_path / "drivers" / "sleeper.py").write_text(textwrap.dedent(DRIVER))
    (tmp_path / "configs" / "toy.json").write_text(json.dumps(
        {"answer": 42}))
    (tmp_path / "workloads" / "toy.sleep.json").write_text(json.dumps(
        {"config": "toy", "driver": "sleeper", "chips": 1,
         "traffic": {"unit_s": 0.01}}))
    spec = json.load(open(os.path.join(run.ROOT, "BENCHMARK.json")))
    spec["end_to_end"].append({"name": "unit_s", "unit": "s",
                               "better": "lower", "bound": 0.05,
                               "source": "host_clock",
                               "workloads": ["toy.sleep"]})
    bench_json = tmp_path / "BENCHMARK.json"
    bench_json.write_text(json.dumps(spec))

    found = run.resolve("toy.sleep", root=str(tmp_path),
                        bench_json=str(bench_json))
    assert {m["name"] for m in found["end_to_end"]} == {"unit_s", "setup_s"}
    assert found["per_layer"] == []

    rc = run.main(["--workload", "toy.sleep", "--seed", "7",
                   "--seconds", "0.1"], root=str(tmp_path),
                  bench_json=str(bench_json), require_chip=False,
                  compile_cache=False)
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and result["correct"] and result["attempted"] >= 5
    assert set(result["metrics"]) == {"unit_s", "setup_s"}
    assert list(result)[-1] == "checks"
    assert result["checks"] == {"answer_gap": {"value": 0, "limit": 0}}


def test_each_cell_reports_what_benchmark_json_names():
    spec = json.load(open(os.path.join(run.ROOT, "BENCHMARK.json")))
    for cell in spec["workloads"]:
        e2e, per_layer = run.cell_metrics(spec, cell["name"])
        names = {m["name"] for m in e2e}
        assert "setup_s" in names and len(names) >= 2
        assert per_layer and all(m["moves"] in names for m in per_layer)
        for m in per_layer:
            assert os.path.isfile(os.path.join(run.BENCH_DIR, "metrics",
                                               m["name"] + ".py"))
        wl = json.load(open(os.path.join(run.BENCH_DIR, "workloads",
                                         cell["name"] + ".json")))
        assert wl["config"] == cell["config"] and wl["chips"] == cell["chips"]
