"""Model step: device time under every `ssm/*` scope (a Mamba-2 mixer's
input projection, convolution, scan, gated norm and output projection
with its residual; forward, backward and recomputation together) over the
device's busy time. Device trace (benchlib/ssm_reduce.py), by the
program's own scope names."""


def read(record):
    from benchlib import ssm_reduce
    return ssm_reduce.share(record)
