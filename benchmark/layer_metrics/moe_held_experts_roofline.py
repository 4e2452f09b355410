"""Kernels: the least time the chip could take for the held experts'
grouped matmuls of the traced steps, at the token-slots those steps'
experts really received (the program's counter, per step and layer;
benchlib.flops_mla_moe: forward, remat's second forward and the
backward's two products per matmul, larger of FLOPs over peak and bytes
over peak per call), over the device time under `moe/experts`. The scope
also holds the held weights' casts, the silu-mul and the zeroing of the
rows of groups held elsewhere, both over all N x k rows: the held expert
block's efficiency, not the kernel's alone. `bound` says which limit
holds for most of the least time."""


def roofline(record):
    from benchlib import flops_mla_moe, subscope_reduce

    static = record.get("static", {})
    call, peaks = static.get("held_experts_call"), static.get("peaks")
    rows = (record.get("counters") or {}).get("traced_held_slots")
    found = subscope_reduce.seconds(record, "moe", ("experts",))
    if not (call and peaks and rows and found):
        return None
    least, bound = flops_mla_moe.held_experts_least_time_s(
        call["model"], rows, call["remat"], peaks)
    return {"share": 100.0 * least / found[0], "bound": bound}


def read(record):
    out = roofline(record)
    return None if out is None else out["share"]
