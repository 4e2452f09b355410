"""Model step: device time under `kda/delta` (the gated delta rule: the
L2 norms, the chunks' key-key and query-key blocks, the triangular
inverses, the chained state; all phases) over the device's busy time.
Device trace (benchlib/kda_reduce.py)."""


def read(record):
    from benchlib import kda_reduce
    return kda_reduce.share(record, ("kda/delta",))
