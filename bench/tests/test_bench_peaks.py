"""The peaks table, and the refusal to measure anywhere but on a chip
that is in it."""
import json
import os
import types

import jax
import pytest

import bench.run as run

PEAKS = os.path.join(run.BENCH_DIR, "peaks.json")


def fake(kind, platform="tpu", n=1):
    return [types.SimpleNamespace(platform=platform, device_kind=kind)] * n


def test_table_has_its_source_and_the_v5e_peaks():
    table = json.load(open(PEAKS))
    assert "Google Cloud" in table["source"]
    v5e = table["devices"]["TPU v5 lite"]
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9


def test_a_chip_in_the_table_is_taken(monkeypatch):
    monkeypatch.setattr(jax, "devices", lambda: fake("TPU v5 lite", n=4))
    devices, peaks = run.chip_devices(4, PEAKS)
    assert len(devices) == 4 and peaks["bf16_flops_per_s"] == 197e12


@pytest.mark.parametrize("devices,chips", [
    (fake("cpu", platform="cpu"), 1),     # no accelerator
    (fake("TPU v9 unknown"), 1),          # a chip not in the table
    (fake("TPU v5 lite"), 4),             # fewer chips than the cell asks
])
def test_anything_else_is_refused(monkeypatch, devices, chips):
    monkeypatch.setattr(jax, "devices", lambda: devices)
    with pytest.raises(run.NoChip):
        run.chip_devices(chips, PEAKS)


@pytest.mark.parametrize("workload", ["fed36-internvl2-1b.round"])
def test_a_run_on_the_cpu_exits_nonzero_and_prints_no_result(capsys,
                                                             workload):
    rc = run.main(["--workload", workload, "--seed", "1", "--seconds", "1"])
    out = capsys.readouterr()
    assert rc != 0 and out.out == ""
    assert "no TPU" in out.err
