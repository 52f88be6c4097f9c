"""Kernels: the forest kernel's share of its roofline, in percent.

The least time the chip could take for the window's forest work (the
longer of its required operations over the compute peak and its bytes
over the HBM peak, bench/work.py) over the summed device time of the
``_forest_kernel`` launches in the trace (custom calls named
``forest_score_pallas``), on every device of the cell."""

from bench import work


def read(record):
    s = record.summary
    if s is None or s.kernel_s <= 0 or record.service["docs"] <= 0:
        return None
    least = work.least_seconds(record.forest_ops(), record.forest_bytes(),
                               record.peak)
    return 100.0 * least / s.kernel_s
