"""Process set-up: seconds rank 0's train worker spent in the compiler before
the window: its `jax.compile` spans with `cache=miss` (compiled and
written), `small` (compiled, too small or too quick to keep) or `off`, the
summed short ones included (`benchlib/setup_spans.py`)."""

NAME = "setup_backend_compile_s"


def read(record):
    from benchlib import setup_spans
    return setup_spans.read(record, NAME)


def why_nothing(record):
    from benchlib import setup_spans
    return setup_spans.why_nothing(record, NAME)
