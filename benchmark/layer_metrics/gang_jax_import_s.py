"""Train gang: seconds the slowest worker of the first gang spent in
`train.worker.jax_import` (the compile cache's placement and the process's
first `import jax`), under the driver's `train.gang.backend`. The workers'
flight recorders, kept past the gang (`benchlib/setup_spans.py`)."""

NAME = "gang_jax_import_s"


def read(record):
    from benchlib import setup_spans
    return setup_spans.read(record, NAME)


def why_nothing(record):
    from benchlib import setup_spans
    return setup_spans.why_nothing(record, NAME)
