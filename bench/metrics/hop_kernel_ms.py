"""hop_kernel_ms: device time of the ``chain_combine`` kernel in one hop,
in ms.

The operations under the program's ``chain_combine`` scope inside the hop
program (``jit_safe_hop``), summed over the window's runs and divided by
the runs. With ``hop_copy_ms`` it makes up ``hop_ms``. Moves ``round_s``.
"""
from bench.lib import scopes


def read(t):
    found = scopes.scoped_s(t, "jit_safe_hop", "CHAIN_COMBINE")
    if found is None or not found[1]:
        return None
    seconds, runs = found
    return 1e3 * seconds / runs
