"""hop_roofline: the least HBM time one hop needs over its device time, %.

A hop reads the incoming cipher (4 B/word) and the learner's update
(4 B/word) and writes the outgoing cipher (4 B/word): 12 B/word, however
it is implemented. Its two pads need one Threefry-2x32 block per two
words each, but the chip's integer VPU rate is not among its published
peaks, so that bound is left out and this is the HBM roofline alone.
Moves ``round_s``.
"""

#: HBM bytes a hop needs per word of the update
HOP_BYTES_PER_WORD = 12


def hop_bytes(words: int) -> int:
    return HOP_BYTES_PER_WORD * words


def threefry_blocks(words: int) -> int:
    """Threefry-2x32 blocks one hop needs: one per two words per pad,
    two pads."""
    return 2 * -(-words // 2)


def read(t):
    seconds, runs = t.module_s("jit_safe_hop")
    if not runs:
        return None
    need_s = hop_bytes(t.counts["update_words"]) / t.peaks["hbm_bytes_per_s"]
    return 100.0 * need_s / (seconds / runs)
