"""Kernels: the least time the chip could take for the expert FFN's
grouped matmuls of the traced steps (forward, remat's second forward and
the backward's two products per matmul; larger of FLOPs over peak FLOP/s
and bytes over peak bytes/s per call, benchlib.flops_moe) over the device
time under the sub-scope `moe/experts`, which also holds the silu-mul and
the expert weights' casts: the expert block's efficiency, not the
kernel's alone. `bound` says which limit holds for the larger call."""


def roofline(record):
    from benchlib import flops_moe, moe_reduce

    static = record.get("static", {})
    call, peaks = static.get("experts_call"), static.get("peaks")
    reduced = moe_reduce.for_record(record)
    steps = (record.get("trace") or {}).get("modules_per_device")
    if not (call and peaks and reduced and steps):
        return None
    took = reduced["sub_s"].get("experts", 0.0)
    if not took:
        return None
    least, bound = flops_moe.experts_least_time_s(
        call["model"], call["tokens"], call["remat"], peaks)
    return {"share": 100.0 * steps * least / took, "bound": bound}


def read(record):
    out = roofline(record)
    return None if out is None else out["share"]
