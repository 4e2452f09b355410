"""Train loop: of the measured window's steps' time, the percent that stall
steps had over the median step (a step: the start of one `train.step` span
on the loop thread to the next; a stall step: longer than 1.1 medians); 0
with no stall step. One stall of 350 ms in a 30 s window is 1.2%: what
moves `train_tokens_per_s` by its bound at a time. The train worker's
flight recorder, kept past the gang (`benchlib/window_spans.py`)."""

NAME = "window_stall_share"


def read(record):
    from benchlib import window_spans
    return window_spans.read(record, NAME)


def why_nothing(record):
    from benchlib import window_spans
    return window_spans.why_nothing(record, NAME)
