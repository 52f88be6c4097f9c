"""Batcher: percent of the document slots of the window's engine calls
(query bucket × document bucket per flush) that carry no real document
(``BatcherStats``: padded_doc_slots ÷ doc_slots). A program without these
counters reads nothing."""


def read(record):
    b = record.batcher
    if b.get("doc_slots", 0) <= 0:
        return None
    return 100.0 * b["padded_doc_slots"] / b["doc_slots"]
