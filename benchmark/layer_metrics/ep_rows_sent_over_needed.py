"""Collectives: rows that left a chip, padding included
(`moe_exchange_rows_sent`), over the token-slots routed to another chip
(`moe_exchange_rows_needed`), summed over the window's steps, the layers
and the chips. 1 is a ragged exchange; bounded buckets send their bound.
From the program's own counters in the step's metrics."""


def read(record):
    from benchlib import ep_reduce
    return ep_reduce.rows_sent_over_needed(record)
