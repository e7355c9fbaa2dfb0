"""Records ``cpu_scope_window.xplane.pb``, the small trace the scope
attribution test reads: five steps of a jitted program with one named
scope on the CPU, each in a step span, inside the harness's window span.

The scope (the program's ``keystream`` name) holds a sine that XLA fuses
with a subtraction outside it, so the fusion's root is unscoped and only
its fused computation carries the scope; a matrix product follows,
outside the scope.

    JAX_PLATFORMS=cpu python bench/tests/data/record_cpu_scope_trace.py
"""
import glob
import os
import shutil
import sys
import tempfile

ROOT = os.path.join(os.path.dirname(__file__), *[".."] * 3)
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench.lib.trace import WINDOW_SPAN  # noqa: E402
from repro.obs.trace import KEYSTREAM  # noqa: E402

STEPS = 5


def program(x):
    with jax.named_scope(KEYSTREAM):
        pad = jnp.sin(x) * 3.0
    return (x - pad) @ x.T


def main():
    f = jax.jit(program)
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    out = tempfile.mkdtemp()
    jax.profiler.start_trace(out)
    with jax.profiler.TraceAnnotation(WINDOW_SPAN):
        for i in range(STEPS):
            with jax.profiler.StepTraceAnnotation("step", step_num=i):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(out, "plugins", "profile", "*",
                                 "*.xplane.pb"))[0]
    shutil.copy(src, os.path.join(os.path.dirname(__file__),
                                  "cpu_scope_window.xplane.pb"))
    shutil.rmtree(out)


if __name__ == "__main__":
    main()
