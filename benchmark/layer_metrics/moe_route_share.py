"""Model step: device time under `moe/router`, `moe/dispatch` and
`moe/combine` — router logits, softmax and top-k, the sort and the counts,
the gathers into expert order and back, the weighted sum: the memory- and
latency-bound part of the expert layer — over the device's busy time, all
phases. Device trace (benchlib/moe_reduce.py)."""


def read(record):
    from benchlib import moe_reduce
    return moe_reduce.share(record, ("router", "dispatch", "combine"))
