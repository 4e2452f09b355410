"""Kernels: the least time the chip could take for the flash kernels'
calls (larger of FLOPs over peak FLOP/s and bytes over peak bytes/s, from
the calls' shapes, benchlib.flops) over the time their events took. Every
call counts, remat's second forward included: this is the kernel's
efficiency, not the model's. At these shapes all three are compute-bound
(`bound` below says so per kind)."""


def roofline(record):
    from benchlib import flops

    trace = record.get("trace") or {}
    static = record.get("static", {})
    kinds = (trace.get("kernel_s") or {}).get("attn")
    peaks, call = static.get("peaks"), static.get("attention_call")
    if not (kinds and peaks and call):
        return None
    least = took = 0.0
    bound = {}
    for kind, (seconds, count) in kinds.items():
        if not count:
            continue
        shape = (call["batch"], call["heads"], call["seq"],
                 call["head_dim"])
        t, which = flops.least_time_s(
            flops.flash_call_flops(kind, *shape),
            flops.flash_call_bytes(kind, *shape), peaks)
        least += t * count
        took += seconds
        bound[kind] = which
    if not took:
        return None
    return {"share": 100.0 * least / took, "bound": bound}


def read(record):
    out = roofline(record)
    return None if out is None else out["share"]
