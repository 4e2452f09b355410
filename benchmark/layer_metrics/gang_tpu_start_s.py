"""Train gang: seconds the slowest worker of the first gang spent starting its
runtime: `train.worker.tpu_start` (`jax.local_devices()`, libtpu's start)
plus `train.worker.distributed_init` where the gang runs
`jax.distributed.initialize`. The workers' flight recorders, kept past the
gang (`benchlib/setup_spans.py`)."""

NAME = "gang_tpu_start_s"


def read(record):
    from benchlib import setup_spans
    return setup_spans.read(record, NAME)


def why_nothing(record):
    from benchlib import setup_spans
    return setup_spans.why_nothing(record, NAME)
