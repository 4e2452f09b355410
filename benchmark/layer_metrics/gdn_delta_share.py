"""Model step: device time under `gdn/delta` (the gated delta rule behind
one decay a head: the L2 norms, the chunks' key-key and query-key blocks
under their `[C, C]` decays, the triangular inverses, the chained state;
all phases) over the device's busy time. Device trace
(benchlib/gdn_reduce.py)."""


def read(record):
    from benchlib import gdn_reduce
    return gdn_reduce.share(record, ("gdn/delta",))
