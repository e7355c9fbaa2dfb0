"""The program's compile counter (``repro.launch.compile_cache``), fed
into one registry for the whole run.

The harness loads a cell's metric readers before it sets the cell up, so
a reader that imports this module starts the count before the first
compile. With a program that has no such counter, ``REGISTRY`` is None
and nothing is read.

The harness reads the registry only after the window, so this module
also notes when jax last compiled (its own listener, beside the
program's): a compile that ended after the window began means the total
holds more than the set-up.
"""
from __future__ import annotations

import time

try:
    from repro.launch.compile_cache import COMPILE_SECONDS, watch_compiles
except ImportError:
    REGISTRY = None
else:
    import jax

    from repro.obs import MetricsRegistry
    REGISTRY = watch_compiles(MetricsRegistry())

#: wall-clock time (``time.time()``) at which jax's last compile event ended
last_compile_s = float("-inf")


def _note(event: str, seconds: float, **_) -> None:
    global last_compile_s
    if event.startswith(("/jax/core/compile/", "/jax/compilation_cache/")):
        last_compile_s = time.time()


if REGISTRY is not None:
    jax.monitoring.register_event_duration_secs_listener(_note)


def setup_seconds(window_start_s: float | None) -> float | None:
    """Compile seconds taken before a window that began at wall-clock
    ``window_start_s`` (``time.time()``): the counter's total, read after
    the window. None without the counter, and where a compile ended after
    the window began, since the total then holds more than the set-up."""
    if REGISTRY is None or window_start_s is None:
        return None
    if last_compile_s >= window_start_s:
        return None
    return float(REGISTRY.counter(COMPILE_SECONDS).value)
