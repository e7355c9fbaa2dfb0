"""chain_ms.safe_step: device time of the train step's secure aggregation,
in ms a step.

The operations under the program's ``safe_chain`` scope inside the step
program (``jit_train_step``): the encode, the pads and the initiator's
mask, the ring's hops, the unmask, the decode and the broadcast of the
mean; each counted once (``bench.lib.nesting``), summed over the
window's steps and divided by the steps. Moves ``round_s``.
"""
from bench.lib import scopes
from bench.lib.nesting import outermost_view


def read(t):
    found = scopes.scoped_s(outermost_view(t), "jit_train_step", "SAFE_CHAIN")
    if found is None or not found[1]:
        return None
    seconds, runs = found
    return 1e3 * seconds / runs
