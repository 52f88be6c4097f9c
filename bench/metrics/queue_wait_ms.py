"""Batcher: mean milliseconds a request waited in the queue, from its
submit to the start of its flush, over the requests handed to an engine
call in the traced window (``BatcherStats``: queue_wait_s ÷ queue_waited).
A program without these counters reads nothing."""


def read(record):
    b = record.batcher
    if b.get("queue_waited", 0) <= 0:
        return None
    return 1e3 * b["queue_wait_s"] / b["queue_waited"]
