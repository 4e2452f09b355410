"""Model step: device time under `moe/shared` (the shared expert every
token passes: its two matmuls, silu-mul and the weights' casts) over the
device's busy time, all phases. Device trace
(benchlib/subscope_reduce.py)."""


def read(record):
    from benchlib import subscope_reduce
    return subscope_reduce.share(record, "moe", ("shared",))
