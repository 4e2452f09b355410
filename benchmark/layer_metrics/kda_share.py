"""Model step: device time under `kda_norm` and every `kda/*` scope (a
Kimi Delta Attention sublayer: its norm, the projections, the three
convolutions, the gates, the delta rule, the head norm with its gate and
the output projection with its residual; forward, backward and
recomputation together) over the device's busy time. Device trace
(benchlib/kda_reduce.py), by the program's own scope names."""


def read(record):
    from benchlib import kda_reduce
    return kda_reduce.share(record)
