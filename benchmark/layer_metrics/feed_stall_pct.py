"""Device feed: share of the learner thread's wait + busy time it spent
waiting for a batch, from `DeviceFeed.stats()` deltas over the window.
Host-clock wait shares of the learner thread, not device busy time."""


def read(record):
    feed = record.get("counters", {}).get("device_feed")
    if not feed:
        return None
    total = feed["feed_wait_s"] + feed["learner_busy_s"]
    return 100.0 * feed["feed_wait_s"] / total if total else None
