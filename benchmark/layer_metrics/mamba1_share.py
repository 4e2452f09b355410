"""Model step: device time under every `ssm/*` scope of a Mamba-1 mixer
(`ssm/in_proj`, `ssm/conv`, `ssm/x_proj`, `ssm/scan`, `ssm/gate`,
`ssm/out_proj` with its residual; forward, backward and recomputation
together) over the device's busy time. Device trace
(benchlib/sambay_reduce.py), by the program's own scope names."""


def read(record):
    from benchlib import sambay_reduce
    return sambay_reduce.share(record, sambay_reduce.SSM)
