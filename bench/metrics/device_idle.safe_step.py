"""device_idle.safe_step: share of the traced window in which no operation
ran on a chip, averaged over the cell's chips, %. Moves ``round_s``."""


def read(t):
    return t.idle_pct()
