"""Model step: device time under every `mhc/*` scope (a residual path of
several streams: each sublayer's maps, the read and the write of the
streams, the entry and the exit; forward, backward and recomputation
together) over the device's busy time. Device trace
(benchlib/mhc_reduce.py), by the program's own scope names."""


def read(record):
    from benchlib import mhc_reduce
    return mhc_reduce.share(record)
