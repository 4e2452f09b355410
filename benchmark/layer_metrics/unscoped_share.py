"""Device: device time of ops that are no collective and carry no scope of
the vocabulary (what the names fail to name) over the device's busy time.
Device trace."""


def read(record):
    from benchlib import scope_reduce
    return scope_reduce.share(record, (scope_reduce.UNSCOPED,))
