"""Model step: device time under `moe/router`, `moe/dispatch`,
`moe/experts` and `moe/combine` together (scoring all the experts and the
top-k, the choice of the slots that can be held, the sort and the
gathers, the held experts' grouped matmuls, the weighted sum) over the
device's busy time, all phases: `moe_held_share`'s reading of the device
trace (benchlib/subscope_reduce.py), under this cell's name."""

from benchlib.spec import load_module

read = load_module("layer_metrics", "moe_held_share").read
