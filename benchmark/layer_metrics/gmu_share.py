"""Model step: device time under `gmu/in_proj`, `gmu/gate` and
`gmu/out_proj` (a gated memory unit: the stream's projection, its silu
times the memory, the output projection with its residual; all phases)
over the device's busy time. Device trace (benchlib/sambay_reduce.py)."""


def read(record):
    from benchlib import sambay_reduce
    return sambay_reduce.share(record, sambay_reduce.GMU)
