"""Train loop: of the measured window's steps' time, the percent that is
stall steps' excess which nothing recorded accounts for: no span of 1 ms
or more on another thread or process (`gc.collect`, `rpc.server`, the
driver's) overlaps it beyond what overlaps the other steps, and
`train.report`'s `blocked_s` does not cover it. 0 with no stall step; equal
to `window_stall_share` when every stall is the device's, the runtime's
or the host's (`benchlib/window_spans.py`)."""

NAME = "window_stall_unnamed_share"


def read(record):
    from benchlib import window_spans
    return window_spans.read(record, NAME)


def why_nothing(record):
    from benchlib import window_spans
    return window_spans.why_nothing(record, NAME)
