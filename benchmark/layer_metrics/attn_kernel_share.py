"""Kernels: device time of the attention kernels' events (forward, dq,
dkv, or a backward in one call; the configuration's `kernels.attn` names
them per kind, for every library it knows) over the device's busy time."""


def read(record):
    trace = record.get("trace") or {}
    kinds = (trace.get("kernel_s") or {}).get("attn")
    if not kinds or not trace.get("busy_s"):
        return None
    seconds = sum(s for s, _ in kinds.values())
    return 100.0 * seconds / trace["busy_s"] if seconds else None


def why_nothing(record):
    """run.py prints this where `read` returned None."""
    from benchlib import scope_reduce
    return scope_reduce.describe_attention(record)
