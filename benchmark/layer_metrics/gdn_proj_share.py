"""Model step: device time under `gdn/qkv_proj`, `gdn/conv`, `gdn/gates`,
`gdn/out_norm`, `gdn/out_proj` and `gdn_post_norm` (a Gated DeltaNet mixer
without its delta rule: the projections with their weights' casts, the
convolution with silu, the decay, beta and the output gate, the head
norm, the norm on the mixer's output, the residual) over the device's
busy time, all phases. Device trace (benchlib/gdn_reduce.py)."""


def read(record):
    from benchlib import gdn_reduce
    return gdn_reduce.share(record, tuple(
        name for name in gdn_reduce.SCOPES if name != "gdn/delta"))
