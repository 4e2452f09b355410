"""Model step: device time under `loop/exit_gate` (each pass's normed
hidden state times the gate's gain, float32) and `loop/exit_loss` (the
log-sigmoids, the exit distribution, its entropy, the passes' hidden
states laid out as rows for the head, the weighting; forward and
backward together) over the device's busy time. Device trace
(benchlib/loop_reduce.py), by the program's own scope names."""


def read(record):
    from benchlib import loop_reduce
    return loop_reduce.share(record, loop_reduce.EXIT)
