"""Model step: device time under `moe/router`, `moe/dispatch`,
`moe/experts` and `moe/combine` together: routing over all the experts,
the sort and the gathers of every token-slot, the held experts' grouped
matmuls: the routed path of a chip that holds a share of the experts;
over the device's busy time, all phases. Device trace
(benchlib/subscope_reduce.py)."""


def read(record):
    from benchlib import subscope_reduce
    return subscope_reduce.share(
        record, "moe", ("router", "dispatch", "experts", "combine"))
