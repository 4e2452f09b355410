"""Kernels: the least time the chips could take for the experts' grouped
matmuls of the traced steps at the rows each chip's experts really
received (the program's `moe_rows_received`, per step, layer and chip;
benchlib.flops_ep_moe: forward, remat's second forward and the backward's
two products per matmul, larger of FLOPs over peak and bytes over peak per
call, each chip's 16 experts' weights) over the time of the `gmm` and
`tgmm` kernels' events, mean over the chips. `bound` says which limit
holds for most of the least time."""


def roofline(record):
    from benchlib import ep_reduce
    return ep_reduce.experts_roofline(record)


def read(record):
    out = roofline(record)
    return None if out is None else out["share"]
