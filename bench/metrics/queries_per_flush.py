"""Batcher: queries completed per flush over the traced window
(``BatcherStats``: completed / full + deadline + drain flushes)."""


def read(record):
    flushes = record.flushes
    if flushes <= 0:
        return None
    return record.batcher["completed"] / flushes
