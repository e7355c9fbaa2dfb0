#!/usr/bin/env python3
"""Chip benchmark of SAFE: one cell, one run.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name. ``bench/workloads/<cell>.json`` names the
cell's configuration (``bench/configs/<config>.json``), its driver
(``bench/drivers/<driver>.py``) and its traffic. ``BENCHMARK.json``, at
the root of the checkout, says which metrics the cell reports; each
per-layer metric is read by ``bench/metrics/<metric>.py``.

A run builds its inputs on the device from ``--seed``, warms up every
program its window uses (set-up), runs the cell back to back for
``--seconds``, and then checks what the window produced against a plain
reference. With ``--trace 0`` it reports the cell's end-to-end metrics,
with ``--trace 1`` its per-layer metrics, read from a profiler trace of
the window. The last line of stdout is one JSON object; the numbers
compared, each beside its limit, are the last lines of stderr.

It runs only on a TPU whose ``device_kind`` is in ``bench/peaks.json``:
otherwise it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
# the TPU runtime logs to /tmp/tpu_logs unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench.lib import trace as tr  # noqa: E402
from bench.lib.cell import Context  # noqa: E402

#: profiler output of a traced run (inside the checkout, fixed path)
TRACE_DIR = os.path.join(ROOT, ".bench_trace")


class NoChip(RuntimeError):
    """The run found no device it may measure on."""


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    """The module in ``path``, loaded once per process."""
    mod = sys.modules.get(name)
    if mod is not None and mod.__file__ == path:
        return mod
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    sys.modules[name] = mod
    return mod


def find(root: str, kind: str, name: str, ext: str) -> str:
    """``<root>/<kind>/<name><ext>``, else the one under ``bench/``."""
    for base in (root, BENCH_DIR):
        path = os.path.join(base, kind, name + ext)
        if os.path.isfile(path):
            return path
    raise FileNotFoundError(f"no {kind[:-1]} named {name!r}: "
                            f"{os.path.join(root, kind, name + ext)}")


def cell_metrics(spec: dict, workload: str) -> tuple[list, list]:
    """The end-to-end and per-layer metric entries this cell reports."""
    def listed(m):
        return "workloads" not in m or workload in m["workloads"]
    e2e = [m for m in spec["end_to_end"] if listed(m)]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if (workload in m["workloads"] if "workloads" in m
                     else m["moves"] in names)]
    return e2e, per_layer


def resolve(workload: str, *, root: str = BENCH_DIR,
            bench_json: str | None = None) -> dict:
    """Everything the cell's name leads to: its workload, configuration,
    driver module, metric entries and metric readers."""
    spec = load_json(bench_json or os.path.join(ROOT, "BENCHMARK.json"))
    wl = load_json(find(root, "workloads", workload, ".json"))
    config = load_json(find(root, "configs", wl["config"], ".json"))
    driver = load_module(find(root, "drivers", wl["driver"], ".py"),
                         f"bench_driver_{wl['driver']}")
    e2e, per_layer = cell_metrics(spec, workload)
    readers = {m["name"]: load_module(find(root, "metrics", m["name"], ".py"),
                                      f"bench_metric_{m['name']}")
               for m in per_layer}
    return {"workload": wl, "config": config, "driver": driver,
            "end_to_end": e2e, "per_layer": per_layer, "readers": readers}


def chip_devices(chips: int, peaks_path: str) -> tuple[list, dict]:
    """The first ``chips`` TPU devices and their peaks; NoChip otherwise."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devs[0].platform!r}); "
                     f"this benchmark measures only on the chip")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    kind = devs[0].device_kind
    peaks = load_json(peaks_path)["devices"]
    if kind not in peaks:
        raise NoChip(f"device_kind {kind!r} is not in {peaks_path}")
    return devs[:chips], peaks[kind]


def enable_cache() -> str:
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    where = enable_compile_cache()
    # every program, however quick to compile, comes from the cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


def memory_peak(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def measure(args, found: dict, *, devices: list, peaks: dict | None) -> dict:
    """Set up, run the window, check; returns the result line's object."""
    import jax
    wl = found["workload"]
    ctx = Context(workload=args.workload, config=found["config"],
                  traffic=wl["traffic"], seed=args.seed, chips=wl["chips"],
                  devices=devices)
    cell = found["driver"].setup(ctx)
    if args.trace:
        seconds = min(args.seconds, wl.get("trace_seconds", args.seconds))
        out = os.path.join(TRACE_DIR, args.workload)
        shutil.rmtree(out, ignore_errors=True)
        jax.profiler.start_trace(out)
        with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
            win = cell.run_window(seconds)
        jax.profiler.stop_trace()
    else:
        win = cell.run_window(args.seconds)
    setup_s = win.start - T0
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": memory_peak(devices)}

    metrics, breakdown = {}, None
    if args.trace:
        view = tr.view(tr.load(tr.find_xplane(out)), len(devices), win.units,
                       cell.counts, peaks)
        device["busy_s"] = view.busy_s()
        device["window_s"] = view.window_s
        breakdown = view.breakdown()
        for m in found["per_layer"]:
            value = found["readers"][m["name"]].read(view)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        taken = dict(win.metrics, setup_s=setup_s)
        for m in found["end_to_end"]:
            if m["name"] not in taken:
                raise KeyError(f"driver {wl['driver']!r} took no "
                               f"{m['name']!r} in cell {args.workload!r}")
            metrics[m["name"]] = {"value": taken[m["name"]], "unit": m["unit"]}

    cell.release()
    checks = cell.check()
    result = {"correct": all(c.ok for c in checks), "attempted": win.units,
              "failed": sum(not c.ok for c in checks), "metrics": metrics,
              "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {c.name: c.as_json() for c in checks}
    for c in checks:
        print(f"check {c.name} = {c.value!r} limit {c.limit!r} "
              f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr, flush=True)
    return result


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, *, root: str = BENCH_DIR, bench_json: str | None = None,
         require_chip: bool = True, compile_cache: bool = True) -> int:
    """``require_chip=False`` measures on whatever JAX finds (tests)."""
    args = parse(argv)
    found = resolve(args.workload, root=root, bench_json=bench_json)
    chips = found["workload"]["chips"]
    try:
        if require_chip:
            devices, peaks = chip_devices(chips,
                                          os.path.join(BENCH_DIR, "peaks.json"))
        else:
            import jax
            devices, peaks = jax.devices()[:chips], None
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    if compile_cache:
        print(f"bench: compile cache {enable_cache()}", file=sys.stderr)
    result = measure(args, found, devices=devices, peaks=peaks)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
