"""Model step: the rows the fullest chip's experts received over the mean
over the chips (`moe_rows_received`), median over the window's steps and
layers: the straggler, whose grouped matmuls the other chips wait for at
the next exchange. 1 is an even load between chips. From the program's
own counter in the step's metrics."""


def read(record):
    from benchlib import ep_reduce
    return ep_reduce.chip_rows_max_over_mean(record)
