"""Model step: the most loaded expert's token-slots over the mean load
(tokens x k / experts), the worst layer of a step, median over the
window's steps. From the program's own counter `moe_tokens_per_expert`
in the step's metrics, read by the loop with the loss. 1 is even routing;
the grouped matmul's tiles see this skew."""


def read(record):
    import statistics
    loads = (record.get("counters") or {}).get("expert_load_max_over_mean")
    return statistics.median(loads) if loads else None
