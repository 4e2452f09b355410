"""Collectives: the part of the exchange's time (`ep_exchange_share`) in
which no compute operation runs on that device, as
`collective_exposed_share` reads every collective's; over the device's
busy time, mean over the chips. Device trace (benchlib/ep_reduce.py)."""


def read(record):
    from benchlib import ep_reduce
    found = ep_reduce.shares(record)
    return None if found is None else found["exposed"]
