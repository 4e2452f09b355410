"""Train loop: the largest `blocked_s` of the measured window's
`train.report` spans, in ms: the longest the driver's result round held the
loop (`report_wait_ms` is the traced steps' median of the span itself).
The train worker's flight recorder (`benchlib/window_spans.py`)."""

NAME = "report_wait_max_ms"


def read(record):
    from benchlib import window_spans
    return window_spans.read(record, NAME)


def why_nothing(record):
    from benchlib import window_spans
    return window_spans.why_nothing(record, NAME)
