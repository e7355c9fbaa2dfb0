"""device_idle.round: share of the traced window in which no operation
ran on the chip, %. Moves ``round_s``."""


def read(t):
    return t.idle_pct()
