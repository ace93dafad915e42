"""Device layer: the share of the traced window in which no op ran on the chip."""


def read(s):
    return 100.0 * (1.0 - s.busy_s / s.window_s)
