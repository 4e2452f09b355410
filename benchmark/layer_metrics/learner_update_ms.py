"""Learner: `learner_busy_s` delta over updates delta: the learner
thread's host-clock time inside `LearnerGroup.update` per update."""


def read(record):
    counters = record.get("counters", {})
    feed, updates = counters.get("device_feed"), counters.get("updates")
    if not feed or not updates:
        return None
    return 1e3 * feed["learner_busy_s"] / updates
