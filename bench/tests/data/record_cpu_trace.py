"""Records ``cpu_window.xplane.pb``, the small trace the trace-reduction
test reads: five steps of a jitted program on the CPU, each in a step
span, inside the harness's window span.

    JAX_PLATFORMS=cpu python bench/tests/data/record_cpu_trace.py
"""
import glob
import os
import shutil
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), *[".."] * 3))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench.lib.trace import WINDOW_SPAN  # noqa: E402

STEPS = 5


def main():
    f = jax.jit(lambda x: jnp.sin(x) @ x.T)
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
                                  "cpu_window.xplane.pb"))
    shutil.rmtree(out)


if __name__ == "__main__":
    main()
