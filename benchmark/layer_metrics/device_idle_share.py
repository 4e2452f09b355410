"""Device: 1 - union of the operations' intervals over the traced window,
averaged over the chips. Device trace of a few steady steps."""


def read(record):
    trace = record.get("trace") or {}
    if not trace.get("window_s"):
        return None
    return 100.0 * trace["idle_s"] / trace["window_s"]
