"""Persistent XLA compilation cache for the entry points that run on a chip,
and a count of what jax compiles.

``JAX_COMPILATION_CACHE_DIR``, when set, wins: jax reads it itself and
nothing is set here. Otherwise the cache lives at ``<checkout>/.jax_cache``
— a fixed path, because the path is part of the cache key, so a directory
named after a temp dir, pid or time would never hit.

``watch_compiles`` feeds a ``repro.obs.MetricsRegistry`` from jax's own
compile events, which jax records only while it traces, lowers, compiles
or reads a program from the cache: a steady state that compiles nothing
pays nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

#: one per compile, cache hits included (the event spans the cache read)
COMPILES = "jax_compiles_total"
#: seconds spent tracing, lowering and compiling or reading the cache
COMPILE_SECONDS = "jax_compile_seconds_total"

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
# the backend-compile event spans the cache read, so the cache read's own
# event is not added again; tracing and lowering come before it and do
# not overlap it
_TIMED = (_BACKEND_COMPILE, "/jax/core/compile/jaxpr_trace_duration",
          "/jax/core/compile/jaxpr_to_mlir_module_duration")

_watched: list = []  # the registries fed, each once

#: ``<checkout>/.jax_cache`` (this file is ``<checkout>/src/repro/launch/``).
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)


def _on_duration(event: str, seconds: float, **_) -> None:
    if event == _BACKEND_COMPILE:
        for reg in _watched:
            reg.counter(COMPILES).inc()
    if event in _TIMED:
        for reg in _watched:
            reg.counter(COMPILE_SECONDS).inc(seconds)


def watch_compiles(registry):
    """Count jax's compiles into ``registry`` from now on; returns it.

    The jax listener is registered once per process and a registry is fed
    once however often it is passed. The series start at zero, so a
    snapshot taken now reads them.
    """
    if not _watched:
        import jax
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
    if not any(r is registry for r in _watched):
        _watched.append(registry)
        for name in (COMPILES, COMPILE_SECONDS):
            registry.counter(name)
    return registry
