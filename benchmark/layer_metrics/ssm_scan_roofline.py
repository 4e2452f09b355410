"""Kernels: the least time the chip could take for the mixers' selective
scans of the traced steps (benchlib.flops_ssm_moe: per mixer a forward,
remat's second forward and a backward of two forwards, as PR 31 counts
attention's; per pass the larger of the chunked scan's FLOPs over peak and
its least bytes over peak) over the device time under `ssm/scan`, which
also holds softplus, the decays' exponentials and the D skip: the scan
block's efficiency. Plain XLA einsums today, no kernel: far from the
peak. `bound` says which limit holds."""


def roofline(record):
    from benchlib import flops_ssm_moe, ssm_reduce

    static = record.get("static", {})
    call, peaks = static.get("scan_call"), static.get("peaks")
    steps = (record.get("trace") or {}).get("modules_per_device")
    found = ssm_reduce.seconds(record, ("scan",))
    if not (call and peaks and steps and found and found[0]):
        return None
    least, bound = flops_ssm_moe.scan_least_time_s(
        call["model"], call["tokens"], steps, call["remat"], peaks)
    return {"share": 100.0 * least / found[0], "bound": bound}


def read(record):
    out = roofline(record)
    return None if out is None else out["share"]
