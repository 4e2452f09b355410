"""Model step: device time under `ssm/in_proj`, `ssm/conv`,
`ssm/gate_norm` and `ssm/out_proj` (a mixer without its scan: the two
projections with their weights' casts, the depthwise convolution with its
document mask and silu, the gated norm over all 4,096 channels, the
scaled residual) over the device's busy time, all phases:
`ssm_proj_share`'s reading under this cell's name. Device trace
(benchlib/ssm_reduce.py)."""

from benchlib.spec import load_module

read = load_module("layer_metrics", "ssm_proj_share").read
