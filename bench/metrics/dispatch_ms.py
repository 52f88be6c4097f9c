"""Engine step: mean host milliseconds per engine call before its one
device read: placement, engine config and the compiled step's dispatch,
then the eager ops after it (``ServiceStats``: dispatch_s + post_s, timed
by the program with ``time.perf_counter``, ÷ batches). A program without
these counters reads nothing."""


def read(record):
    s = record.service
    if "dispatch_s" not in s or "post_s" not in s or s["batches"] <= 0:
        return None
    return 1e3 * (s["dispatch_s"] + s["post_s"]) / s["batches"]
