"""Kernels: the least time the chip could take for the attention kernels'
calls (larger of FLOPs over peak FLOP/s and bytes over peak bytes/s, from
the calls' shapes, benchlib.flops) over the time their events took. Every
call counts, remat's second forward included: this is the kernel's
efficiency, not the model's. A call counts as what it computed, whichever
library it is from: a backward that runs as `bwd_dkv` events with no
`bwd_dq` event made dQ in the same call and is `bwd_fused`, five products
where the two separate calls make seven (`flops.kinds_as_computed`). At
the cells' shapes every kind is compute-bound (`bound` says so per kind)."""


def roofline(record):
    from benchlib import flops

    trace = record.get("trace") or {}
    static = record.get("static", {})
    kinds = (trace.get("kernel_s") or {}).get("attn")
    peaks, call = static.get("peaks"), static.get("attention_call")
    if not (kinds and peaks and call):
        return None
    least = took = 0.0
    bound, calls, by_kind = {}, {}, {}
    for kind, (seconds, count) in flops.kinds_as_computed(kinds).items():
        if not count:
            continue
        shape = (call["batch"], call["heads"], call["seq"],
                 call["head_dim"])
        t, which = flops.least_time_s(
            flops.attention_call_flops(kind, *shape),
            flops.attention_call_bytes(kind, *shape, call.get("kv_heads")),
            peaks)
        least += t * count
        took += seconds
        bound[kind] = which
        calls[kind] = count
        by_kind[kind] = 100.0 * t * count / seconds
    if not took:
        return None
    return {"share": 100.0 * least / took, "bound": bound, "calls": calls,
            "by_kind": by_kind}


def read(record):
    out = roofline(record)
    return None if out is None else out["share"]


def why_nothing(record):
    """run.py prints this where `read` returned None."""
    from benchlib import scope_reduce
    return scope_reduce.describe_attention(record)
