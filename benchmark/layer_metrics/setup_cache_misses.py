"""Process set-up: how many programs rank 0's train worker compiled and wrote
to the persistent cache before the window (`jax.compile` with `cache=miss`):
a warm run of an unchanged tree reads 0 (`benchlib/setup_spans.py`)."""

NAME = "setup_cache_misses"


def read(record):
    from benchlib import setup_spans
    return setup_spans.read(record, NAME)


def why_nothing(record):
    from benchlib import setup_spans
    return setup_spans.why_nothing(record, NAME)
