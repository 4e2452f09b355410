"""Process set-up: seconds in the driver's `cluster.init` span:
`ray_tpu.init()` from its first import down to the node's registration
answered (the GCS, the node manager, the worker pool's start). The driver's
flight recorder (`benchlib/setup_spans.py`)."""

NAME = "cluster_init_s"


def read(record):
    from benchlib import setup_spans
    return setup_spans.read(record, NAME)


def why_nothing(record):
    from benchlib import setup_spans
    return setup_spans.why_nothing(record, NAME)
