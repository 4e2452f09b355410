"""Model step: the documents of a step's packed sequences (the program's
counter `packed_docs`: the runs of equal `segment_ids` among the step's
inputs), median over the window's steps. Program counter."""


def read(record):
    import statistics

    docs = record.get("counters", {}).get("packed_docs")
    return float(statistics.median(docs)) if docs else None
