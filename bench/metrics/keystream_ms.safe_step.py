"""keystream_ms.safe_step: device time of the jnp keystream in one train
step, in ms.

The operations under the program's ``keystream`` scope inside the step
program (``jit_train_step``): every learner's hop pads and initiator mask
(``crypto.prf.keystream_pair_lanes``), with what XLA fuses with them;
each counted once (``bench.lib.nesting``), summed over the window's steps
and divided by the steps. A part of ``chain_ms.safe_step``. Moves
``round_s``.
"""
from bench.lib import scopes
from bench.lib.nesting import outermost_view


def read(t):
    found = scopes.scoped_s(outermost_view(t), "jit_train_step", "KEYSTREAM")
    if found is None or not found[1]:
        return None
    seconds, runs = found
    return 1e3 * seconds / runs
