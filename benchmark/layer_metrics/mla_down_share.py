"""Model step: device time under `qkv/q_down` and `qkv/kv_down` (latent
attention's two down-projections, each with its latent's RMS norm) over
the device's busy time, all phases. Device trace
(benchlib/subscope_reduce.py), by the program's own scope names."""


def read(record):
    from benchlib import subscope_reduce
    return subscope_reduce.share(record, "qkv", ("q_down", "kv_down"))
