"""What the harness hands a driver, and what a driver hands back.

A driver (``bench/drivers/<name>.py``) defines ``setup(ctx) -> cell``.
The cell has ``run_window(seconds) -> Window``, ``release()`` (drop the
program's state so the reference fits) and ``check() -> [Check]``, and
the attribute ``counts``: the work counts its per-layer metrics read.
A driver may also define ``control(ctx, seeds) -> [dict]``, the readings
of its correctness control (``bench/control.py``).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable


@dataclasses.dataclass
class Context:
    workload: str
    config: dict      # bench/configs/<config>.json
    traffic: dict     # the workload file's "traffic"
    seed: int
    chips: int
    devices: list     # the jax devices the cell uses


@dataclasses.dataclass
class Window:
    """A measured window: ``units`` rounds or steps run back to back from
    ``start`` to ``end`` (host perf_counter seconds), and the end-to-end
    metrics the driver took over it."""
    start: float
    end: float
    units: int
    metrics: dict


@dataclasses.dataclass
class Check:
    """One number compared with its limit. ``ok`` is ``value <= limit``."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return bool(self.value <= self.limit)

    def as_json(self) -> dict[str, Any]:
        return {"value": self.value, "limit": self.limit}


def back_to_back(unit: Callable[[int], Any], seconds: float,
                 metric: str) -> Window:
    """Runs ``unit(0)``, ``unit(1)``, ... until ``seconds`` have passed
    (each unit ends when its result is ready); ``metric`` is the window's
    time over the units it ran."""
    start = time.perf_counter()
    done = 0
    while True:
        unit(done)
        done += 1
        now = time.perf_counter()
        if now - start >= seconds:
            return Window(start, now, done, {metric: (now - start) / done})


def seed_words(seed: int) -> tuple[int, int]:
    """A seed of up to 64 bits as two uint32 words (jax.random.key keeps
    only the low 32 bits of a larger int)."""
    seed = int(seed)
    if seed < 0 or seed >= 2**64:
        raise ValueError(f"seed {seed} is outside [0, 2**64)")
    return seed & 0xFFFFFFFF, seed >> 32


def seed_key(seed: int, *tags: int):
    """jax PRNG key from the whole seed, folded with ``tags``."""
    import jax
    lo, hi = seed_words(seed)
    key = jax.random.fold_in(jax.random.key(lo), hi)
    for t in tags:
        key = jax.random.fold_in(key, t)
    return key


def seed_rng(seed: int, *tags: int):
    """numpy Generator from the whole seed and ``tags``."""
    import numpy as np
    return np.random.default_rng([*seed_words(seed), *tags])
