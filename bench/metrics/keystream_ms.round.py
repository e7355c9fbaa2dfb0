"""keystream_ms.round: device time of the jnp keystream in one round, in ms.

The operations under the program's ``keystream`` scope in every program
of the window (the initiator's private mask R in ``jit_safe_initiate``,
the last edge pad and R again in ``jit_safe_unmask``), divided by the
rounds the window ran. An operation counts whole when any instruction it
runs is the keystream's, so what XLA fuses with it counts too: the
unmask program's subtraction, and in ``jit_safe_initiate`` the copy that
slices ``mask_add``'s output back to V words (a tile copy that no copy
metric counts; a change that removes the initiator's tile copies can
lower this metric with the keystream untouched). Moves ``round_s``.
"""
from bench.lib import scopes


def read(t):
    found = scopes.scoped_s(t, "", "KEYSTREAM")
    if found is None or not t.units:
        return None
    return 1e3 * found[0] / t.units
