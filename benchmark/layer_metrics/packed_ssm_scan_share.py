"""Model step: device time under `ssm/scan` (softplus of dt, the
documents' marks, the chunked selective scan with its resets and the D
skip) over the device's busy time, all phases: `ssm_scan_share`'s reading
under this cell's name. Device trace (benchlib/ssm_reduce.py)."""

from benchlib.spec import load_module

read = load_module("layer_metrics", "ssm_scan_share").read
