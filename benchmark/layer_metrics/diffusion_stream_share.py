"""Model step: device time under `diffusion/noise` (the draws, the
replacement by the mask id, the weights and their counts) and
`diffusion/stream` (the doubled stream's concatenation and positions, the
split before the final norm; forward and backward together) over the
device's busy time. Device trace (benchlib/blockdiff_reduce.py), by the
program's own scope names."""


def read(record):
    from benchlib import blockdiff_reduce
    return blockdiff_reduce.share(record)
