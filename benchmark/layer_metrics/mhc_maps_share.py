"""Model step: device time under `mhc/maps` (the RMS statistic over the
n*C values of a token, the float32 product with phi, the sigmoids and the
Sinkhorn rounds of every sublayer's three maps; forward, backward and
recomputation together) over the device's busy time. Device trace
(benchlib/mhc_reduce.py), by the program's own scope names."""


def read(record):
    from benchlib import mhc_reduce
    return mhc_reduce.share(record, (mhc_reduce.MAPS,))
