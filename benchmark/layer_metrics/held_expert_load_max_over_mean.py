"""Model step: the most loaded held expert's token-slots over the mean of
the held experts' loads, the worst layer of a step, median over the
window's steps. From the program's own counter `moe_tokens_per_expert`
(held experts only, where a share is held). 1 is even routing among the
held; the grouped matmul's row tiles see this skew."""


def read(record):
    import statistics
    loads = (record.get("counters") or {}).get(
        "held_expert_load_max_over_mean")
    return statistics.median(loads) if loads else None
