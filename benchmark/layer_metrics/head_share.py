"""Model step: device time under the scopes `head` (vocab projection) and
`loss` (softmax cross-entropy and the chunking around it) over the
device's busy time. Device trace, by the program's own scope names."""


def read(record):
    from benchlib import scope_reduce
    return scope_reduce.share(record, ("head", "loss"))
