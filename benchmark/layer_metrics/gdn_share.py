"""Model step: device time under every `gdn/*` scope and `gdn_post_norm`
(a Gated DeltaNet mixer: the projections, the convolution, the gates, the
delta rule, the head norm under its gate, the output projection with the
norm on it and the residual; forward, backward and recomputation
together) over the device's busy time. Device trace
(benchlib/gdn_reduce.py), by the program's own scope names."""


def read(record):
    from benchlib import gdn_reduce
    return gdn_reduce.share(record)
