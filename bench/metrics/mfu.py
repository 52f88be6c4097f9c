"""Whole step: the window's required operations (bench/work.py) as a
percent of what the cell's chips could do at their peak in the window."""


def read(record):
    s = record.summary
    if s is None or s.window_s <= 0 or record.service["docs"] <= 0:
        return None
    return 100.0 * record.forest_ops() / (
        s.window_s * record.chips * record.peak.flops)
