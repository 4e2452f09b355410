"""Device: peak memory on the fullest chip as the runtime's allocator
reports it after the window (benchlib.device.memory_peak_bytes: live
buffers plus the reserved program scratch), in GB of 1e9 bytes. The
compiler's own `memory_analysis()` of the step is in the run's stderr
detail; on this runtime it reads higher than the chip holds (PERF.md
section 7)."""


def read(record):
    peak = record.get("device", {}).get("memory_peak_bytes")
    return peak / 1e9 if peak else None
