"""Process set-up: of `setup_backend_compile_s`, the `jax.compile` spans with
`cache=small`: what a warm run compiles again because the cache never kept
it (the eager ops of a reference check, the little programs around the step)
(`benchlib/setup_spans.py`)."""

NAME = "setup_small_compile_s"


def read(record):
    from benchlib import setup_spans
    return setup_spans.read(record, NAME)


def why_nothing(record):
    from benchlib import setup_spans
    return setup_spans.why_nothing(record, NAME)
