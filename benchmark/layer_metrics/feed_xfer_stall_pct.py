"""Device feed: the part of the learner thread's wait + busy time spent
blocked on a host-to-device transfer that had not landed, from
`DeviceFeed.stats()` deltas over the window."""


def read(record):
    feed = record.get("counters", {}).get("device_feed")
    if not feed:
        return None
    total = feed["feed_wait_s"] + feed["learner_busy_s"]
    return 100.0 * feed["feed_xfer_s"] / total if total else None
