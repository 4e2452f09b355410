"""Model step: device time under `attention/diff` (differential
attention past the kernels: lambda, `A1 V - lambda A2 V`, the pair norm
and the `1 - lambda_init` scale; all phases) over the device's busy time.
Device trace (benchlib/sambay_reduce.py)."""


def read(record):
    from benchlib import sambay_reduce
    return sambay_reduce.share(record, (sambay_reduce.DIFF,))
