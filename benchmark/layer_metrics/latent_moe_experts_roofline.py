"""Kernels: the least time the chip could take for the held latent
experts' grouped matmuls of the traced steps (latent -> width and width
-> latent, no gate), at the token-slots those steps' experts really
received (the program's counter, per step and layer;
benchlib.flops_ssm_moe: forward, remat's second forward and the
backward's two products per matmul, larger of FLOPs over peak and bytes
over peak per call), over the device time under `moe/experts`. The scope
also holds the held weights' casts, relu^2 and the zeroing of the rows of
groups held elsewhere, over all tokens x min(k, held) rows: the held
expert block's efficiency, not the kernel's alone. `bound` says which
limit holds for most of the least time."""


def roofline(record):
    from benchlib import flops_ssm_moe, subscope_reduce

    static = record.get("static", {})
    call, peaks = static.get("held_experts_call"), static.get("peaks")
    rows = (record.get("counters") or {}).get("traced_held_slots")
    found = subscope_reduce.seconds(record, "moe", ("experts",))
    if not (call and peaks and rows and found and found[0]
            and "moe_latent_size" in call["model"]):
        return None
    least, bound = flops_ssm_moe.held_experts_least_time_s(
        call["model"], rows, call["remat"], peaks)
    return {"share": 100.0 * least / found[0], "bound": bound}


def read(record):
    out = roofline(record)
    return None if out is None else out["share"]
