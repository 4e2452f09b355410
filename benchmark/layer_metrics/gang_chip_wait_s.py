"""Train gang: seconds the slowest worker of the first gang waited for chips a
predecessor still held (`waited_s` of `train.worker.chip_wait`; 0 says they
were free at the first look). The workers' flight recorders, kept past the
gang (`benchlib/setup_spans.py`)."""

NAME = "gang_chip_wait_s"


def read(record):
    from benchlib import setup_spans
    return setup_spans.read(record, NAME)


def why_nothing(record):
    from benchlib import setup_spans
    return setup_spans.why_nothing(record, NAME)
