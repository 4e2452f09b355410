"""Task and object plane: the program's `rpc.*`, `store.*`, `cw.*` and
`envelope.*` spans' exclusive share of the learner thread's wall, over
the tail of the window its span ring still covers (benchlib.span_buckets,
a copy of tools/perf_report.py's bucket arithmetic)."""


def read(record):
    spans = record.get("spans")
    if not spans or not spans.get("covered_s"):
        return None
    return 100.0 * spans["seconds"]["store_rpc"] / spans["covered_s"]
