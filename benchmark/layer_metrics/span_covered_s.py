"""Task and object plane: how many seconds of the window the learner
thread's span ring still covered when it was gathered: what the
span-based shares were reduced over."""


def read(record):
    spans = record.get("spans")
    return spans.get("covered_s") if spans else None
