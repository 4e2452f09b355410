"""Model step: device time under `ssm/scan` (softplus of dt, the chunked
selective scan and the D skip) over the device's busy time, all phases.
Device trace (benchlib/ssm_reduce.py)."""


def read(record):
    from benchlib import ssm_reduce
    return ssm_reduce.share(record, ("scan",))
