"""What the process that holds the chip reads from JAX: the device as JAX
reports it, its memory peak, and the compiles it made. Imports JAX only
inside the functions."""

from __future__ import annotations

from typing import Any, Dict, List

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoAccelerator(RuntimeError):
    """No TPU, or fewer chips than the cell asks for: no result."""


def require_device(chips: int, rehearsal: bool) -> Dict[str, Any]:
    """The device dict of the last line; raises without an accelerator
    (a rehearsal configuration alone may run on the CPU)."""
    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if device["platform"] != "tpu" and not rehearsal:
        raise NoAccelerator(f"JAX found no accelerator: {device}")
    if len(devices) != chips:
        raise NoAccelerator(
            f"the cell needs {chips} chips, JAX sees {device}")
    return device


def count_compiles() -> List[float]:
    """A list that grows by one duration per backend compile of this
    process from now on (a program found in a cache compiles nothing)."""
    import jax

    compiles: List[float] = []

    def on_event(event: str, duration: float, **_kw: Any) -> None:
        if event == COMPILE_EVENT:
            compiles.append(duration)

    jax.monitoring.register_event_duration_secs_listener(on_event)
    return compiles


def memory_peak_bytes() -> int:
    """Peak on the fullest chip. On this runtime the allocator's
    `peak_bytes_in_use` leaves out an executable's scratch, which it
    holds as `bytes_reserved` (PERF.md section 7): the peak is the larger
    of the allocator's own and live buffers plus the reserved scratch.
    0 where the backend reports nothing (the CPU)."""
    import jax

    peak = 0
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peak = max(peak, stats.get("peak_bytes_in_use", 0),
                   stats.get("bytes_in_use", 0)
                   + stats.get("peak_bytes_reserved", 0))
    return int(peak)


def finish_device(device: Dict[str, Any], reduced) -> None:
    """The last line's `device`: the memory peak after the window and,
    from a traced run, the device's busy seconds and the traced window."""
    device["memory_peak_bytes"] = memory_peak_bytes()
    if reduced and reduced.get("devices"):
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]


def trace_window(trace_dir: str, body, host_names,
                 kernels=None) -> Dict[str, Any]:
    """Run `body()` under the profiler inside one `bench_window`
    annotation and return the reduced trace (None if the profiler wrote
    nothing). Python call tracing is off: the annotations are kept."""
    import glob
    import shutil

    import jax

    from benchlib import trace_reduce

    shutil.rmtree(trace_dir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_ANNOTATION):
            body()
    finally:
        jax.profiler.stop_trace()
    files = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
    if not files:
        return None
    return trace_reduce.reduce_trace(
        trace_reduce.from_xplane(files[0], host_names), kernels)
