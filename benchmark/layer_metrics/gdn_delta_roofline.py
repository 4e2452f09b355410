"""Kernels: the least time the chip could take for the mixers' delta
rules of the traced steps (benchlib.flops_gdn.delta_least_time_s: per
layer a forward, remat's second forward and a backward of two forwards,
counted from the model's shapes: tokens, heads, d_k, d_v, the chunk; per
pass the larger of the chunked algorithm's FLOPs over peak and its least
bytes over peak) over the device time under `gdn/delta`: the op's
efficiency, whatever implements it. Plain XLA today (batched einsums, a
triangular inverse by halves, a `lax.scan` over the chunks), no kernel:
far from the peak. `bound` says which limit holds."""


def roofline(record):
    from benchlib import flops_gdn, gdn_reduce

    static = record.get("static", {})
    call, peaks = static.get("delta_call"), static.get("peaks")
    steps = (record.get("trace") or {}).get("modules_per_device")
    if not (call and peaks and steps):
        return None
    found = gdn_reduce.seconds(record, ("gdn/delta",))
    if not found or not found[0]:
        return None
    least, bound = flops_gdn.delta_least_time_s(call, steps, peaks)
    return {"share": 100.0 * least / found[0], "bound": bound}


def read(record):
    out = roofline(record)
    return None if out is None else out["share"]
