"""Process set-up: how many `jax.compile` spans of any outcome start inside the
measured window on the loop thread: a shape that changed. 0 in a run whose
warm-up covered every shape (`benchlib/setup_spans.py`)."""

NAME = "window_compiles"


def read(record):
    from benchlib import setup_spans
    return setup_spans.read(record, NAME)


def why_nothing(record):
    from benchlib import setup_spans
    return setup_spans.why_nothing(record, NAME)
