"""Model step: device time under `ssm/scan` of a Mamba-1 mixer (the
chunked selective scan and the D skip) over the device's busy time, all
phases. Device trace (benchlib/sambay_reduce.py)."""


def read(record):
    from benchlib import sambay_reduce
    return sambay_reduce.share(record, ("ssm/scan",))
