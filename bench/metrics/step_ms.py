"""Engine step: mean host milliseconds of the batcher worker's engine calls
(``RankingService.rank_batch``, its one device read included), timed by
the harness's span around each call in the traced window."""


def read(record):
    if not record.steps_s:
        return None
    return 1e3 * sum(record.steps_s) / len(record.steps_s)
