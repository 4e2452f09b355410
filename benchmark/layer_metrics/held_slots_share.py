"""Model step: the token-slots routed to the experts held here over all
tokens x k, summed over the expert layers of a step, median over the
window's steps; percent. From the program's own counters
`moe_tokens_per_expert` and `moe_slots_elsewhere` in the step's metrics.
held / E of it is an even share; the deployment's chip sees E / held
times as many rows from the other chips' batches."""


def read(record):
    import statistics
    shares = (record.get("counters") or {}).get("held_slots_share")
    return statistics.median(shares) if shares else None
