"""Collectives: time in which a collective runs on a device (a blocking
one on the operations' line, or an asynchronous one between its start and
its done) while no compute operation does, over the executed programs'
time. Device trace, averaged over the chips. Absent on one chip."""


def read(record):
    trace = record.get("trace") or {}
    if record.get("static", {}).get("chips", 1) < 2:
        return None
    if not trace.get("module_s"):
        return None
    return 100.0 * trace["collective_exposed_s"] / trace["module_s"]
