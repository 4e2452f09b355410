"""Model step: device time under the scope `loops` and under no scope
nested in it (the loop over a looped stack's passes itself: what the
passes save stacked and sliced, the passes' weight gradients summed into
the loop's f32 carry; forward and backward together) over the device's
busy time. Device trace (benchlib/loop_reduce.py), by the program's own
scope names."""


def read(record):
    from benchlib import loop_reduce
    return loop_reduce.share(record, (loop_reduce.CARRY,))
