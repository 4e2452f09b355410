"""Train loop: over the traced steps, the end of the loop's wait for the
device (the step's `host_sync.*` annotation on the host line) less the end
of the step's last `XLA Modules` event on any chip, in ms, the median: how
late the loop learns that the device is done, the part of `step_gap_ms`
that no name covered. One clock, the device trace's; the host's and the
device's lines agree to about a millisecond
(`benchlib/window_spans.sync_lag_ms`)."""


def read(record):
    from benchlib import window_spans
    return window_spans.sync_lag_for_record(record)


def why_nothing(record):
    return ("no `host_sync.*` annotation waited for a program of this "
            "run's device trace: no trace of this run, or a program whose "
            "loss read is no span")
