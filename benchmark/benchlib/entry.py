"""What a job hands to another process of the program (a train worker, an
actor): importable there as `benchlib.entry` because run.py puts the
benchmark's directory on PYTHONPATH before the cluster starts."""

from __future__ import annotations

from typing import Any, Dict


def worker_entry(config: Dict[str, Any]) -> None:
    """Runs `worker_loop(config)` of the cell's job file."""
    from benchlib.spec import load_module
    load_module("jobs", config["config"]["job"]).worker_loop(config)
