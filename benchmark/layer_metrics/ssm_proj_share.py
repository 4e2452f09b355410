"""Model step: device time under `ssm/in_proj`, `ssm/conv`,
`ssm/gate_norm` and `ssm/out_proj` (a mixer without its scan: the two
projections with their weights' casts, the depthwise convolution with
silu, the gated grouped norm, the residual) over the device's busy time,
all phases. Device trace (benchlib/ssm_reduce.py)."""


def read(record):
    from benchlib import ssm_reduce
    return ssm_reduce.share(
        record, ("in_proj", "conv", "gate_norm", "out_proj"))
