"""Collectives: the least time to move the traced steps' distinct (token,
other chip) pairs' rows (`moe_exchange_pairs` x hidden_size x 2 B) OUT of
a chip four times a layer and step (dispatch and combine, forward and
backward) at the published ICI rate a chip (`peaks.json`'s
`ici_bits_per_s` / 8), over the time of the chip's exchange collectives:
the worst chip. Counted from the pairs and the model's shapes alone
(benchlib/flops_ep_moe.py), so it reads the same work whether rows go a
slot or a chip at a time, padded or ragged, sent again under remat or
kept; what is sent beyond the pairs is time, not work."""


def roofline(record):
    from benchlib import ep_reduce
    return ep_reduce.exchange_roofline(record)


def read(record):
    out = roofline(record)
    return None if out is None else out["share"]
