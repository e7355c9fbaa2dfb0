#!/usr/bin/env python3
"""Readings of a cell's correctness control, on the chip, at full size.

    python bench/control.py --workload <cell> --seeds <n> [<n> ...]

The control is the cell's plain reference put in the program's place one
precision below what the configuration states. It prints one JSON line
per seed with the numbers the cell's check compares; each limit must lie
below what these read. The benchmark's own runs never run it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import run  # noqa: E402
from bench.lib.cell import Context  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    found = run.resolve(args.workload)
    wl = found["workload"]
    try:
        devices, _ = run.chip_devices(
            wl["chips"], os.path.join(run.BENCH_DIR, "peaks.json"))
    except run.NoChip as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    run.enable_cache()
    ctx = Context(workload=args.workload, config=found["config"],
                  traffic=wl["traffic"], seed=args.seeds[0], chips=wl["chips"],
                  devices=devices)
    limits = found["config"]["limits"]
    for reading in found["driver"].control(ctx, args.seeds):
        reading["limits"] = {k: limits[k] for k in reading if k in limits}
        print(json.dumps(reading), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
