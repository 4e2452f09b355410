"""Kernels: the least time the chip could take for the Mamba-1 mixers'
selective scans of the traced steps (benchlib.flops_sambay: per mixer a
forward, remat's second forward and a backward of two forwards; per pass
the larger of the recurrence's operations over the peak and its least
bytes over the peak) over the device time under `ssm/scan`: the scan
block's efficiency. Plain XLA today (a chunked scan with the state
carried), no kernel: far from the peak. `bound` says which limit holds."""


def roofline(record):
    from benchlib import flops_sambay, sambay_reduce

    static = record.get("static", {})
    call, peaks = static.get("scan_call"), static.get("peaks")
    steps = (record.get("trace") or {}).get("modules_per_device")
    if not (call and peaks and steps and "mamba" in call.get("model", {})):
        return None
    found = sambay_reduce.seconds(record, ("ssm/scan",))
    if not found or not found[0]:
        return None
    least, bound = flops_sambay.scan_least_time_s(
        call["model"], call["tokens"], steps, call["remat"], peaks)
    return {"share": 100.0 * least / found[0], "bound": bound}


def read(record):
    out = roofline(record)
    return None if out is None else out["share"]
