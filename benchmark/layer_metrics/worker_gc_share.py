"""Train loop: seconds of the train worker's `gc.collect` spans (a full
collection, or any of a millisecond or more, on whichever thread ran it:
the collector holds every thread of the process) inside the measured
window, in percent of the window's steps' time
(`benchlib/window_spans.py`)."""

NAME = "worker_gc_share"


def read(record):
    from benchlib import window_spans
    return window_spans.read(record, NAME)


def why_nothing(record):
    from benchlib import window_spans
    return window_spans.why_nothing(record, NAME)
