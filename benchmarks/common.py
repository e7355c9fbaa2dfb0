"""Shared benchmark plumbing: timing, CSV rows, result persistence."""
from __future__ import annotations

import json
import os
import time
from typing import Callable

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "results",
                           "benchmarks")

_rows: list[tuple] = []
_payloads: dict = {}


def emit(name: str, us_per_call: float, derived: str = "") -> None:
    """One CSV row: name,us_per_call,derived."""
    _rows.append((name, us_per_call, derived))
    print(f"{name},{us_per_call:.2f},{derived}", flush=True)


def rows():
    return list(_rows)


def save_json(name: str, payload) -> str:
    """Record a module's detailed result payload.

    Unprefixed names no longer write their own ``<name>.json`` — that
    produced stale twins drifting beside the schema'd files (ISSUE 8).
    Instead the payload is stashed and folded into the module's
    ``BENCH_<module>.json`` under the ``payloads`` key by the next
    :func:`save_bench_json` (the ``run.py --bench-json`` harness or a
    ``standalone_bench`` run). Only ``BENCH_``-prefixed names touch
    disk; tests/test_benchmarks.py rejects any other write.
    """
    if not name.startswith("BENCH_"):
        _payloads[name] = payload
        return ""
    os.makedirs(RESULTS_DIR, exist_ok=True)
    path = os.path.join(RESULTS_DIR, f"{name}.json")
    with open(path, "w") as f:
        json.dump(payload, f, indent=1)
    return path


#: stable machine-readable schema version for BENCH_<name>.json files —
#: bump only on breaking layout changes so perf-trajectory tooling can
#: parse every historical run.
BENCH_SCHEMA = "safe-bench/v1"


def save_bench_json(name: str, bench_rows: list, status: str,
                    wall_s: float) -> str:
    """Write results/benchmarks/BENCH_<name>.json with the stable schema:

    {"schema": "safe-bench/v1", "name": ..., "status": "ok"|"failed",
     "wall_s": ..., "rows": [{"name", "us_per_call", "derived"}, ...],
     "payloads": {<save_json name>: <payload>, ...}}

    ``payloads`` drains every :func:`save_json` stash accumulated since
    the previous drain — the module's detailed dicts travel inside its
    schema'd file instead of as unprefixed twins. Additive to
    ``safe-bench/v1``: readers of ``rows`` are unaffected.
    """
    payload = {
        "schema": BENCH_SCHEMA,
        "name": name,
        "status": status,
        "wall_s": wall_s,
        "rows": [{"name": n, "us_per_call": us, "derived": d}
                 for (n, us, d) in bench_rows],
    }
    if _payloads:
        payload["payloads"] = dict(_payloads)
        _payloads.clear()
    return save_json(f"BENCH_{name}", payload)


def standalone_bench(key: str, fn: Callable) -> None:
    """Run one benchmark module standalone (``python -m benchmarks.X``)
    with the same stable ``BENCH_<key>.json`` emission the ``run.py``
    harness performs — so a module run on its own still feeds the
    machine-readable perf trajectory instead of only its legacy JSON."""
    before = len(rows())
    t0 = time.time()
    status = "ok"
    try:
        fn()
    except Exception as e:  # noqa: BLE001
        status = "failed"
        print(f"# FAILED {key}: {e!r}", flush=True)
        raise
    finally:
        save_bench_json(key, rows()[before:], status, time.time() - t0)


def run_device_subprocess(code: str, devices: int = 8,
                          timeout: int = 1800) -> dict:
    """Run benchmark ``code`` in a child python on N virtual CPU devices
    and parse its ``print("JSON" + json.dumps(payload))`` sentinel line.

    jax locks the host device count at first init, so anything needing a
    mesh runs in a subprocess with XLA_FLAGS set before jax imports —
    the shared boilerplate of multi_session / net_load style benches.
    These are host-device rehearsals: the child is pinned to the CPU
    backend, so it never competes with a parent for the chip.
    """
    import subprocess
    import sys
    import textwrap

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.path.join(repo, "src")
    proc = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          capture_output=True, text=True, timeout=timeout,
                          env=env)
    if proc.returncode != 0:
        raise RuntimeError(proc.stderr[-2000:])
    return json.loads(proc.stdout.split("JSON", 1)[1])


def wall(fn: Callable, repeats: int = 3) -> float:
    """Median wall time of fn() in seconds."""
    ts = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return ts[len(ts) // 2]
