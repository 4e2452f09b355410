"""Model step: device time under `qkv/assemble`: what latent attention
costs that is no matmul: RoPE on the rotary columns, the shared rotary
key head's broadcast to every head, the concatenations into q and k, the
layout constraints; over the device's busy time, all phases. Device trace
(benchlib/subscope_reduce.py)."""


def read(record):
    from benchlib import subscope_reduce
    return subscope_reduce.share(record, "qkv", ("assemble",))
