"""device_idle.lookup: share of the traced window in which no operation
ran on the chip (1 - union of op intervals / window), in percent."""


def read(obs):
    t = obs.trace
    if t is None or not t.chips or t.window_s <= 0:
        return None
    return 100.0 * t.idle_share
