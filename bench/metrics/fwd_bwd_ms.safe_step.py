"""fwd_bwd_ms.safe_step: device time of the train step's forward and
backward pass, in ms a step.

The operations under the program's ``fwd_bwd`` scope inside the step
program (``jit_train_step``), each counted once (a layer scan's body is
not counted again inside the loop that runs it: ``bench.lib.nesting``),
summed over the window's steps and divided by the steps. Moves
``round_s``.
"""
from bench.lib import scopes
from bench.lib.nesting import outermost_view


def read(t):
    found = scopes.scoped_s(outermost_view(t), "jit_train_step", "FWD_BWD")
    if found is None or not found[1]:
        return None
    seconds, runs = found
    return 1e3 * seconds / runs
