"""Learner: the goodput ledger's `productive_step` seconds over all the
seconds it accounted for in the window (the learner thread's ledger,
`_private/goodput.py`)."""


def read(record):
    ledger = record.get("counters", {}).get("goodput_s")
    if not ledger:
        return None
    total = sum(ledger.values())
    return 100.0 * ledger.get("productive_step", 0.0) / total \
        if total > 0 else None
