"""Device: percent of the traced window in which no op ran, averaged over
the cell's devices (1 - union of device op intervals / window)."""


def read(record):
    s = record.summary
    if s is None or s.window_s <= 0:
        return None
    return 100.0 * (1.0 - s.mean_busy_s / s.window_s)
