"""Kernels: the least time the chip could take for the streams' mixing of
the traced steps (benchlib.flops_mhc_moe.mhc_least_time_s: the bytes
`mhc_bytes` says every sublayer MUST move, the stream read and written
once forward, again under remat, its gradient read and written and the
stream read once backward, at the chip's memory bandwidth; the work's
FLOPs are four orders under the compute bound) over the device time under
every `mhc/*` scope: the mixing's efficiency, whatever implements it.
Plain XLA fusions today, no kernel. It cannot read over 100% for a
program that does the work: the function counts the least any program
moves."""


def roofline(record):
    from benchlib import flops_mhc_moe, mhc_reduce

    static = record.get("static", {})
    call, peaks = static.get("mhc_call"), static.get("peaks")
    steps = (record.get("trace") or {}).get("modules_per_device")
    if not (call and peaks and steps):
        return None
    found = mhc_reduce.seconds(record)
    if not found or not found[0]:
        return None
    least = flops_mhc_moe.mhc_least_time_s(call, steps, peaks)
    return {"share": 100.0 * least / found[0], "bound": "memory"}


def read(record):
    out = roofline(record)
    return None if out is None else out["share"]
