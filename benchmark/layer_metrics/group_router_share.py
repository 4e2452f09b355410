"""Model step: device time under `moe/router` (the float32 router
product, the sigmoid, the group ranks and the group-limited top-k, the
chosen scores' gather and normalisation, the counters; all phases) over
the device's busy time, in the cell whose router limits a token to 4 of 8
groups of 64 experts. Device trace (benchlib/moe_reduce.py)."""


def read(record):
    from benchlib import moe_reduce
    return moe_reduce.share(record, ("router",))
