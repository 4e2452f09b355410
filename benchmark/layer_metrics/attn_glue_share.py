"""Kernels: device time under the scope `attention` that is NOT one of the
attention kernels' own events (the configuration's `kernels.attn` patterns,
as `attn_kernel_share` counts them) over the device's busy time: the GQA
`jnp.repeat` of k and v, the transposes into `[B,H,T,D]` and back, and
whatever else XLA puts around the calls. Device trace."""


def read(record):
    from benchlib import scope_reduce
    reduced = scope_reduce.for_record(record)
    kinds = ((record.get("trace") or {}).get("kernel_s") or {}).get("attn")
    if not reduced or not kinds or not reduced["busy_s"] \
            or not any(count for _, count in kinds.values()):
        return None   # no kernel found: the scope less nothing is no glue
    glue = reduced["bucket_s"].get("attention", 0.0) \
        - sum(seconds for seconds, _ in kinds.values())
    return 100.0 * glue / reduced["busy_s"]


def why_nothing(record):
    """run.py prints this where `read` returned None."""
    from benchlib import scope_reduce
    return scope_reduce.describe_attention(record)
