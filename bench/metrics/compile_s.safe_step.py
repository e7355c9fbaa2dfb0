"""compile_s.safe_step: seconds the train-step run spent tracing, lowering
and compiling its programs or reading them from the compile cache before
the window.

The program's ``jax_compile_seconds_total`` (``watch_compiles``), counted
from when the harness loads this reader, before it sets the cell up;
reported only if no compile ended after the window began. Moves
``setup_s``.
"""
from bench.lib import compiles, scopes


def read(t):
    return compiles.setup_seconds(scopes.window_start_s(t))
