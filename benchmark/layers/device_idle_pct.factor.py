"""Idle share of the device over the traced window of a closed loop of
library calls: 100 × (1 − union of device op intervals / window)."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None or tr.window_s <= 0:
        return None
    return 100.0 * tr.idle_share
