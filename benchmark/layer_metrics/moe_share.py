"""Model step: device time under the scope `moe` (router, dispatch, the
experts' grouped matmuls, combine and the residual; forward, backward and
recomputation together) over the device's busy time. Device trace, by the
program's own scope names (benchlib/scope_reduce.py)."""


def read(record):
    from benchlib import scope_reduce
    return scope_reduce.share(record, ("moe",))
