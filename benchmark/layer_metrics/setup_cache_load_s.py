"""Process set-up: seconds rank 0's train worker spent loading programs from
the persistent compilation cache before the window: its `jax.compile` spans
with `cache=hit`, the summed short ones included
(`benchlib/setup_spans.py`)."""

NAME = "setup_cache_load_s"


def read(record):
    from benchlib import setup_spans
    return setup_spans.read(record, NAME)


def why_nothing(record):
    from benchlib import setup_spans
    return setup_spans.why_nothing(record, NAME)
