"""Model step: device time under the scope `optimizer` (`optimizer.update`,
`apply_updates`, `global_norm` in parallel/train_step.py) over the
device's busy time. Device trace, by the program's own scope names."""


def read(record):
    from benchlib import scope_reduce
    return scope_reduce.share(record, ("optimizer",))
