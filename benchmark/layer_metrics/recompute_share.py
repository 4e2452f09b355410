"""Model step: device time of ops whose path holds remat's
`rematted_computation` (a forward run a second time on the way back: the
layers under `remat=True`, the chunked head), in any scope, over the
device's busy time. Device trace."""


def read(record):
    from benchlib import scope_reduce
    return scope_reduce.share(record, phases=("recompute",))
