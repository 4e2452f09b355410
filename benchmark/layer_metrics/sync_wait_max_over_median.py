"""Train loop: a step's seconds in `host_sync.*` spans on the loop thread
(the loop's wait for the device: its read of the loss past `train_step`),
the measured window's largest over its median. 1.0 is a window in which
the device was never late (`benchlib/window_spans.py`)."""

NAME = "sync_wait_max_over_median"


def read(record):
    from benchlib import window_spans
    return window_spans.read(record, NAME)


def why_nothing(record):
    from benchlib import window_spans
    return window_spans.why_nothing(record, NAME)
