"""Model step: device time under `kda/qkv_proj`, `kda/conv`, `kda/gates`,
`kda/out_norm` and `kda/out_proj` (a Kimi Delta Attention sublayer
without its delta rule: the projections with their weights' casts, the
convolutions with silu, the decay, beta and the output gate, the head
norm, the residual) over the device's busy time, all phases. Device
trace (benchlib/kda_reduce.py)."""


def read(record):
    from benchlib import kda_reduce
    return kda_reduce.share(record, tuple(
        "kda/" + name for name in kda_reduce.SUBSCOPES if name != "delta"))
