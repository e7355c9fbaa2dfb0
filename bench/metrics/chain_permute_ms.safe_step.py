"""chain_permute_ms.safe_step: device time of the learner ring's hops, the
``ppermute`` collectives alone, in ms a step.

The operations under the program's ``chain_hop`` scope inside the step
program (``jit_train_step``), each counted once (``bench.lib.nesting``),
summed over the window's steps and divided by the steps. A part of
``chain_ms.safe_step``. Moves ``round_s``.
"""
from bench.lib import scopes
from bench.lib.nesting import outermost_view


def read(t):
    found = scopes.scoped_s(outermost_view(t), "jit_train_step", "CHAIN_HOP")
    if found is None or not found[1]:
        return None
    seconds, runs = found
    return 1e3 * seconds / runs
