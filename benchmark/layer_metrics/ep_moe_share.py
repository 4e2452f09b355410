"""Model step: device time under `moe/router`, `moe/dispatch`,
`moe/exchange`, `moe/experts` and `moe/combine` together, the exchange's
all-to-alls among them (`scope_reduce` books a collective under
`collectives` whatever its scope, so `moe_share` would leave them out),
over the device's busy time, all phases, mean over the chips. Device
trace (benchlib/ep_reduce.py)."""


def read(record):
    from benchlib import ep_reduce
    found = ep_reduce.shares(record)
    return None if found is None else found["moe"]
