"""Model step: device time under the sub-scope `moe/experts` (the two
grouped matmuls with silu-mul, the expert weights' casts; all phases) over
the device's busy time. Device trace (benchlib/moe_reduce.py)."""


def read(record):
    from benchlib import moe_reduce
    return moe_reduce.share(record, ("experts",))
