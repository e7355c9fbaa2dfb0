"""The compile counter (``launch/compile_cache.watch_compiles``): jax's own
compile events fed into a ``repro.obs.MetricsRegistry``."""
import os
import subprocess
import sys
import time

import jax
import numpy as np

from helpers import REPO
from repro.launch import compile_cache as cc
from repro.obs import MetricsRegistry


def counters(reg):
    return reg.snapshot()["counters"]


def test_watching_is_idempotent():
    """Watching again, or watching a second registry, registers no second
    jax listener and feeds no registry twice: one compile counts once in
    each."""
    reg, other = MetricsRegistry(), MetricsRegistry()
    assert cc.watch_compiles(reg) is reg
    assert cc.watch_compiles(reg) is reg
    assert cc.watch_compiles(other) is other
    cc.watch_compiles(reg)
    jax.jit(lambda x: x * 5 - 2)(np.ones(17, np.float32))
    assert counters(reg)[cc.COMPILES] == 1
    assert counters(other)[cc.COMPILES] == 1


def test_a_fresh_compile_counts_once_and_a_cached_call_never():
    reg = cc.watch_compiles(MetricsRegistry())
    assert counters(reg) == {cc.COMPILES: 0, cc.COMPILE_SECONDS: 0}
    f = jax.jit(lambda x: x * 3 + 1)
    x = np.ones(8, np.float32)
    before = time.perf_counter()
    f(x).block_until_ready()
    took = time.perf_counter() - before
    first = counters(reg)
    assert first[cc.COMPILES] == 1
    assert 0 < first[cc.COMPILE_SECONDS] <= took
    for _ in range(3):
        f(x).block_until_ready()
    assert counters(reg) == first
    f(np.ones(9, np.float32)).block_until_ready()  # a new shape compiles
    assert counters(reg)[cc.COMPILES] == 2


def test_cache_reads_are_counted(tmp_path):
    """A program read back from the persistent cache is a compile event
    too, and its seconds count."""
    from jax.experimental.compilation_cache import compilation_cache
    reg = cc.watch_compiles(MetricsRegistry())
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    prev = {k: getattr(jax.config, k) for k in keys}
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    compilation_cache.reset_cache()
    hits = []

    def hit(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            hits.append(event)

    jax.monitoring.register_event_listener(hit)
    try:
        x = np.arange(11, dtype=np.float32)
        jax.jit(lambda v: v * 7 + 3)(x).block_until_ready()
        got = counters(reg)
        assert got[cc.COMPILES] == 1
        jax.clear_caches()
        jax.jit(lambda v: v * 7 + 3)(x).block_until_ready()
    finally:
        jax.monitoring.unregister_event_listener(hit)
        for k, v in prev.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
    assert len(hits) == 1  # the second compile was read from the cache
    again = counters(reg)
    assert again[cc.COMPILES] == 2
    assert again[cc.COMPILE_SECONDS] > got[cc.COMPILE_SECONDS]


def test_the_counter_renders_for_prometheus():
    reg = cc.watch_compiles(MetricsRegistry())
    jax.jit(lambda x: x - 4)(np.ones(5, np.float32))
    text = reg.render_prometheus()
    assert f"# TYPE {cc.COMPILES} counter\n{cc.COMPILES} 1\n" in text
    assert f"# TYPE {cc.COMPILE_SECONDS} counter" in text


def test_obs_imports_no_jax():
    """``repro.obs`` stays a leaf without jax: the scope names are plain
    strings (checked in a fresh interpreter)."""
    code = ("import sys, repro.obs, repro.obs.trace; "
            "print('jax' in sys.modules)")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, check=True,
                         env=dict(os.environ,
                                  PYTHONPATH=os.path.join(REPO, "src")))
    assert out.stdout.strip() == "False"
