"""Device-side profiling: jax.profiler wrappers for TPU traces.

reference parity: the reference's profiling surface is host-side
(py-spy stack dumps / memray via dashboard reporter, `ray timeline`
Chrome traces of task events — dashboard/modules/reporter/
profile_manager.py:11-19, scripts.py:1856). On TPU the interesting
trace is the DEVICE one: XLA op timelines, HBM usage, ICI collectives.
This module exposes jax.profiler with the framework's ergonomics:

    with ray_tpu.util.tpu_profiler.trace("/tmp/prof"):
        train_step(...)

    ray_tpu.util.tpu_profiler.start_server(9012)   # live tensorboard

Traces are TensorBoard-compatible (xplane) directories. Ops carry the
models' `jax.named_scope` vocabulary in their op_name; for a named host
region inside a trace use `ray_tpu._private.spans.traced(name)`, which the
train loop's own `train.step` / `train.report` spans go through.
"""

from __future__ import annotations

import contextlib
import glob
import os
import time
from typing import Iterator, Optional


@contextlib.contextmanager
def trace(log_dir: str,
          create_perfetto_link: bool = False) -> Iterator[str]:
    """Capture a device trace for the with-block into log_dir."""
    import jax
    os.makedirs(log_dir, exist_ok=True)
    jax.profiler.start_trace(log_dir,
                             create_perfetto_link=create_perfetto_link)
    try:
        yield log_dir
    finally:
        jax.profiler.stop_trace()


def start_server(port: int = 9012):
    """Expose the live profiler (connect TensorBoard's profile plugin
    or `jax.profiler.trace_remote`)."""
    import jax
    return jax.profiler.start_server(port)


def latest_trace_dir(log_dir: str) -> Optional[str]:
    """The newest xplane capture under log_dir, if any."""
    pattern = os.path.join(log_dir, "plugins", "profile", "*")
    runs = sorted(glob.glob(pattern), key=os.path.getmtime)
    return runs[-1] if runs else None


def device_memory_profile(path: Optional[str] = None) -> bytes:
    """Current HBM allocation profile (pprof format); written to
    `path` when given (jax.profiler.device_memory_profile)."""
    import jax
    blob = jax.profiler.device_memory_profile()
    if path:
        with open(path, "wb") as f:
            f.write(blob)
    return blob


def profile_step(fn, *args, log_dir: Optional[str] = None, **kwargs):
    """One-shot: run fn under a trace, return (result, trace_dir)."""
    log_dir = log_dir or os.path.join(
        "/tmp", f"ray_tpu_prof_{int(time.time())}")
    with trace(log_dir):
        out = fn(*args, **kwargs)
        # intentional barrier: the trace window must include device
        # completion, or the profile under-reports the step
        import jax
        jax.block_until_ready(out)  # graftlint: disable=RT021
    return out, log_dir
