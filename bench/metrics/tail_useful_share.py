"""Compaction / tail: percent of the rows the tail launches scored that
carry a document continuing past the sentinel (``ServiceStats``:
docs_continued ÷ tail_rows, the rows being the last stage's compaction
capacity padded to the launch's row blocks). A program without the
``tail_rows`` counter, or a window with no tail launch, reads nothing."""


def read(record):
    s = record.service
    if s.get("tail_rows", 0) <= 0:
        return None
    return 100.0 * s["docs_continued"] / s["tail_rows"]
