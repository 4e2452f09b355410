"""Process set-up: seconds rank 0's train worker spent tracing and lowering
before the window: the union, a thread, of its `jax.trace` and `jax.lower`
spans (a trace nests in another) plus what events under 1 ms were summed to
(`benchlib/setup_spans.py`)."""

NAME = "setup_trace_lower_s"


def read(record):
    from benchlib import setup_spans
    return setup_spans.read(record, NAME)


def why_nothing(record):
    from benchlib import setup_spans
    return setup_spans.why_nothing(record, NAME)
