"""Kernels: the least time the chip could take for the mixers' selective
scans of the traced steps (benchlib.flops_granite: a function of the
shapes that reads the same whatever implements the scan; per mixer the
passes that run, a forward, remat's second forward and a backward of two
forwards; per pass the larger of the chunked scan's FLOPs over peak and
its least bytes over peak) over the time the scan kernels' own events
took (the configuration's `kernels.scan`: `ssd_scan_fwd`, `ssd_scan_bwd`;
each event's whole duration, as `trace_reduce` counts a kernel's). Where
no such event ran (the scan in plain XLA) the time is the device time
under `ssm/scan`, which also holds softplus, the marks and the D skip.
Document boundaries take pairs away inside a chunk; the count does not
take them off, as the kernels do not. `bound` says which limit holds."""


def roofline(record):
    from benchlib import flops_granite, ssm_reduce

    static = record.get("static", {})
    trace = record.get("trace") or {}
    call, peaks = static.get("packed_scan_call"), static.get("peaks")
    steps = trace.get("modules_per_device")
    if not (call and peaks and steps):
        return None
    kinds = (trace.get("kernel_s") or {}).get("scan") or {}
    took = sum(seconds for seconds, count in kinds.values() if count)
    of = "kernels"
    if not took:
        found = ssm_reduce.seconds(record, ("scan",))
        took, of = (found[0] if found else 0.0), "scope"
    if not took:
        return None
    least, bound = flops_granite.scan_least_time_s(
        call["model"], call["tokens"], steps, call["remat"], peaks)
    return {"share": 100.0 * least / took, "bound": bound, "time_of": of}


def read(record):
    out = roofline(record)
    return None if out is None else out["share"]
