"""hop_ms: device time of one chain hop, in ms.

The hop program (``jit_safe_hop``: the ``chain_combine`` Pallas call
with its wrapper's pad and slice ops) summed over the window's runs in
the ``XLA Modules`` line, divided by the runs. Moves ``round_s``.
"""


def read(t):
    seconds, runs = t.module_s("jit_safe_hop")
    return 1e3 * seconds / runs if runs else None
