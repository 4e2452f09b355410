"""Train loop: median of the program's `train.report` span over the traced
steps: the loop thread inside `train.report()`, which lasts as long as the
driver's result round holds it in the session's size-1 queue. Read from the
span's annotation on the host line of the device trace."""


def read(record):
    from benchlib import scope_reduce
    return scope_reduce.span_median_ms(record, "train.report")
