"""Model step: device time under every `ssm/*` scope (a Mamba-2 mixer's
input projection, convolution, scan, gated norm and output projection
with its residual, with the document marks and masks built under
`ssm/conv/segments` and `ssm/scan/segments`; forward, backward and
recomputation together) over the device's busy time: `ssm_share`'s
reading under this cell's name, the mixers on packed documents. Device
trace (benchlib/ssm_reduce.py)."""

from benchlib.spec import load_module

read = load_module("layer_metrics", "ssm_share").read
