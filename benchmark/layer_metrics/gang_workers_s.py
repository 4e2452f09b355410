"""Train gang: seconds in the driver's `train.gang.placement` (placement
group committed) and `train.gang.actors` (worker processes up, `node_info`
answered) spans of the cell's gang. The driver's flight recorder."""


def read(record):
    from benchlib import scope_reduce
    return scope_reduce.gang_span_s(("train.gang.placement",
                                     "train.gang.actors"))
