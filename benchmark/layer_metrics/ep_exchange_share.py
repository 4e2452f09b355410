"""Collectives: time of the collectives under `moe/exchange` (the counts'
all-to-all, the overflow's pmax, the rows out and back, forward, remat's
forward and backward; blocking ones by their events, asynchronous ones
from start to done) over the device's busy time, mean over the chips.
Device trace (benchlib/ep_reduce.py)."""


def read(record):
    from benchlib import ep_reduce
    found = ep_reduce.shares(record)
    return None if found is None else found["exchange"]
