"""Model step: device time under `moe/latent` (the two projections around
experts that live in a latent, hidden -> latent before the dispatch and
latent -> hidden after the combine, once a token each, with their
weights' casts) over the device's busy time, all phases. Device trace
(benchlib/subscope_reduce.py)."""


def read(record):
    from benchlib import subscope_reduce
    return subscope_reduce.share(record, "moe", ("latent",))
