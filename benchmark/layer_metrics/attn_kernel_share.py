"""Kernels: device time of the flash attention kernels' events (forward,
dq, dkv; the configuration names them) over the device's busy time."""


def read(record):
    trace = record.get("trace") or {}
    kinds = (trace.get("kernel_s") or {}).get("attn")
    if not kinds or not trace.get("busy_s"):
        return None
    seconds = sum(s for s, _ in kinds.values())
    return 100.0 * seconds / trace["busy_s"] if seconds else None
