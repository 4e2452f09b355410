"""Model step: median over the window of the time from one host read of
the loss to the next (batch, dispatch, step, read). Host clock."""

import statistics


def read(record):
    steps = record.get("clock", {}).get("step_s")
    return statistics.median(steps) * 1e3 if steps else None
