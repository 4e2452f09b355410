"""Kernels: the least time the chip could take for the attention kernels'
calls under the block-diffusion mask, each counted as WHAT THE MASK LEAVES
(benchlib.flops_blockdiff_moe: `L^2 + L*B` pairs of the stream's `4 L^2`,
QK^T and PV at the head width, forward and fused backward, from the
model's shapes alone; the bytes every row once), over the time of their
events (those under `attention/block_diffusion`, benchlib/
blockdiff_reduce.py). It reads the same work whatever block sizes or mask
form compute it: the partial blocks a kernel computes whole and masks are
time, not work (`blockdiff_pairs_computed_over_needed` has their ratio).
A backward that runs as `bwd_dkv` events with no `bwd_dq` event made dQ in
the same call (`flops.kinds_as_computed`). `attn_kernel_roofline` counts
the same events as calls of one causal shape and reads lower here by
construction (PERF.md section 7)."""


def roofline(record):
    from benchlib import blockdiff_reduce, flops, flops_blockdiff_moe

    static = record.get("static", {})
    peaks, call = static.get("peaks"), static.get("blockdiff_call")
    if not (peaks and call):
        return None
    found = blockdiff_reduce.attention_kernels(record)
    if not found:
        return None
    least = took = 0.0
    by_kind, bound = {}, {}
    for kind, (seconds, count) in flops.kinds_as_computed(found).items():
        if not count:
            continue
        t, which = flops_blockdiff_moe.attention_least_time_s(
            kind, call["model"], call["seq"], peaks, call["batch"])
        least += t * count
        took += seconds
        by_kind[kind] = 100.0 * t * count / seconds
        bound[kind] = which
    if not took:
        return None
    return {"share": 100.0 * least / took, "by_kind": by_kind,
            "bound": bound}


def read(record):
    out = roofline(record)
    return None if out is None else out["share"]
