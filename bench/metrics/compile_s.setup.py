"""compile_s.setup: seconds the run spent tracing, lowering and compiling
its programs or reading them from the compile cache before the window.

The program's ``jax_compile_seconds_total`` (``watch_compiles``), which
the harness starts counting when it loads this reader, before it sets the
cell up. Read after the window, it is the total at the window's start
only if no compile ended after that start; otherwise nothing is
reported. Moves ``setup_s``.
"""
from bench.lib import compiles, scopes


def read(t):
    return compiles.setup_seconds(scopes.window_start_s(t))
