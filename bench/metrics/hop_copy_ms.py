"""hop_copy_ms: device time of the hop wrapper's tile copies, in ms.

The operations under the program's ``tile_pad`` and ``tile_slice`` scopes
inside the hop program (``jit_safe_hop``): the pad of the cipher and the
update to whole (rows, 128) tiles and the slice of the output back to V
words, summed over the window's runs and divided by the runs. Moves
``round_s``.
"""
from bench.lib import scopes


def read(t):
    found = scopes.scoped_s(t, "jit_safe_hop", "TILE_PAD", "TILE_SLICE")
    if found is None or not found[1]:
        return None
    seconds, runs = found
    return 1e3 * seconds / runs
