"""Train loop: median idle gap on the device between one executed program
and the next (report, next batch, dispatch). Device trace."""


def read(record):
    trace = record.get("trace") or {}
    gap = trace.get("module_gap_median_s")
    return None if gap is None else gap * 1e3
