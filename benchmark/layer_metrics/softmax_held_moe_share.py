"""Model step: device time under `moe/router`, `moe/dispatch`,
`moe/experts` and `moe/combine` together: the softmax router over all the
experts of the stream's 2L positions, the sort and the gathers of every
token-slot, the held experts' grouped matmuls; over the device's busy
time, all phases. Device trace (benchlib/blockdiff_reduce.py)."""


def read(record):
    from benchlib import blockdiff_reduce
    return blockdiff_reduce.moe_share(record)
