"""Model step: device time under `mhc/pre` and `mhc/post` (what a sublayer
reads, `sum_i H_pre[i] X[i]`, and what it leaves, `H_res X + H_post y`: two
mixes over the widest tensor of the step; forward, backward and
recomputation together) over the device's busy time. Device trace
(benchlib/mhc_reduce.py), by the program's own scope names."""


def read(record):
    from benchlib import mhc_reduce
    return mhc_reduce.share(record, mhc_reduce.MIXES)
