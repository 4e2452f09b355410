"""Train gang: seconds in the driver's `train.gang.backend` span
(`Backend.on_start`: `_setup_worker` on every worker, where JAX is imported
and the TPU runtime starts). The driver's flight recorder."""


def read(record):
    from benchlib import scope_reduce
    return scope_reduce.gang_span_s(("train.gang.backend",))
