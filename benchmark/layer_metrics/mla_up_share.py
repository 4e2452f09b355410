"""Model step: device time under `qkv/q_up` and `qkv/kv_up` (latent
attention's up-projections from the normed latents to the heads' queries,
position-free keys and values) over the device's busy time, all phases.
Device trace (benchlib/subscope_reduce.py)."""


def read(record):
    from benchlib import subscope_reduce
    return subscope_reduce.share(record, "qkv", ("q_up", "kv_up"))
