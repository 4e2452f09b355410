"""Kernels: the least time the chip could take for the attention kernels'
calls, each counted as what IT computed (benchlib.flops_sambay: the pairs
its mask leaves, a window call `T·W - W²/2` where a causal one has
`T²/2`; QK^T at the key width and PV at the value width, twice the key's),
over the time their events took. The events are told apart by the
`attention/window`, `attention/full` or `attention/cross` in their paths;
a backward that runs as `bwd_dkv` events with no `bwd_dq` event made dQ in
the same call (`flops.kinds_as_computed`). `attn_kernel_roofline` counts
the same events as calls of one causal shape a cell and reads lower here
by construction (PERF.md section 7)."""


def roofline(record):
    from benchlib import flops, flops_sambay, sambay_reduce

    static = record.get("static", {})
    peaks, calls = static.get("peaks"), static.get("attention_calls")
    cfg = static.get("model") or {}
    if not (peaks and calls and cfg.get("sliding_window")):
        return None
    found = sambay_reduce.attention_kernels(record)
    if not found:
        return None
    seq, batch = calls[0]["seq"], calls[0]["batch"]
    least = took = 0.0
    by_kind, bound = {}, {}
    for kind, events in found.items():
        for call, (seconds, count) in flops.kinds_as_computed(
                events).items():
            if not count:
                continue
            t, which = flops.least_time_s(
                flops_sambay.attention_call_flops(call, kind, cfg, seq,
                                                  batch),
                flops_sambay.attention_call_bytes(call, cfg, seq, batch),
                peaks)
            least += t * count
            took += seconds
            by_kind[f"{kind}.{call}"] = 100.0 * t * count / seconds
            bound[f"{kind}.{call}"] = which
    if not took:
        return None
    return {"share": 100.0 * least / took, "by_kind": by_kind,
            "bound": bound}


def read(record):
    out = roofline(record)
    return None if out is None else out["share"]
