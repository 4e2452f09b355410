"""Kernels: the least time the chip could take for the attention kernels'
calls of a model that mixes window and full layers, each counted as what
ITS mask leaves (benchlib.flops_ep_moe: a window call `T W - W^2 / 2`
pairs where a causal one has `T^2 / 2`; QK^T and PV at the head width),
over the time their events took. The events are told apart by the
`attention/window` or `attention/full` in their paths
(benchlib/sambay_reduce.py, as `masked_attn_kernel_roofline` reads
Phi's); a backward that runs as `bwd_dkv` events with no `bwd_dq` event
made dQ in the same call (`flops.kinds_as_computed`).
`attn_kernel_roofline` counts the same events as calls of one causal
shape and reads lower here by construction (PERF.md section 7)."""


def roofline(record):
    from benchlib import flops, flops_ep_moe, sambay_reduce

    static = record.get("static", {})
    peaks, call = static.get("peaks"), static.get("ep_call")
    if not (peaks and call):
        return None
    found = sambay_reduce.attention_kernels(record)
    if not found:
        return None
    cfg, seq, batch = call["model"], call["seq"], call["batch"]
    # `kernel_s` is a mean over the chips, its events a total
    chips = static.get("chips", 1)
    least = took = 0.0
    by_kind, bound = {}, {}
    for kind, events in found.items():
        for kernel, (seconds, count) in flops.kinds_as_computed(
                events).items():
            if not count:
                continue
            t, which = flops.least_time_s(
                flops_ep_moe.attention_call_flops(kernel, kind, cfg, seq,
                                                  batch),
                flops_ep_moe.attention_call_bytes(kernel, cfg, seq, batch),
                peaks)
            least += t * count / chips
            took += seconds
            by_kind[f"{kind}.{kernel}"] = 100.0 * t * count / chips / seconds
            bound[f"{kind}.{kernel}"] = which
    if not took:
        return None
    return {"share": 100.0 * least / took, "by_kind": by_kind,
            "bound": bound}


def read(record):
    out = roofline(record)
    return None if out is None else out["share"]
