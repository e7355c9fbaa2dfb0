"""zero1_ms.safe_step: device time of the train step's ZeRO-1 update, in
ms a step.

The operations under the program's ``zero1`` scope inside the step
program (``jit_train_step``): each learner's AdamW update of its slice of
the flat master, the all-gather of the updated slices and their return to
the parameter tree; each counted once (``bench.lib.nesting``), summed
over the window's steps and divided by the steps. With
``fwd_bwd_ms.safe_step`` and ``chain_ms.safe_step`` it makes up the step
program's device time. Moves ``round_s``.
"""
from bench.lib import scopes
from bench.lib.nesting import outermost_view


def read(t):
    found = scopes.scoped_s(outermost_view(t), "jit_train_step", "ZERO1")
    if found is None or not found[1]:
        return None
    seconds, runs = found
    return 1e3 * seconds / runs
