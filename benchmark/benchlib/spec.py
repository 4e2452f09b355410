"""Find a cell's files by the names in BENCHMARK.json. No registry: a name
in the spec IS the file's name under the benchmark's directories."""

from __future__ import annotations

import importlib.util
import json
import os
import sys
from typing import Any, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def load_spec(path: Optional[str] = None) -> Dict[str, Any]:
    return load_json(path or os.path.join(ROOT, "BENCHMARK.json"))


def by_name(entries: List[Dict[str, Any]], name: str, what: str):
    for entry in entries:
        if entry["name"] == name:
            return entry
    raise KeyError(f"no {what} named {name!r}; the spec has "
                   f"{[e['name'] for e in entries]}")


def resolve_cell(spec: Dict[str, Any], workload: str) -> Dict[str, Any]:
    """The cell with its configuration file and traffic mix loaded."""
    cell = dict(by_name(spec["workloads"], workload, "workload"))
    config_entry = by_name(spec["configs"], cell["config"], "configuration")
    config = load_json(os.path.join(ROOT, config_entry["file"]))
    traffic = load_json(os.path.join(
        BENCH_DIR, "traffic", cell["traffic"] + ".json"))
    return {"cell": cell, "config_entry": config_entry, "config": config,
            "traffic": traffic}


def metrics_of(spec: Dict[str, Any], section: str,
               workload: str) -> List[Dict[str, Any]]:
    """Metrics of `section` that exist in this cell: those without a
    `workloads` list, and those that list the cell."""
    return [m for m in spec[section]
            if "workloads" not in m or workload in m["workloads"]]


def load_module(kind: str, name: str):
    """benchmark/<kind>/<name>.py, loaded by file path under a name that
    cannot clash with an installed package."""
    path = os.path.join(BENCH_DIR, kind, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(
            f"{kind} {name!r} needs the file {path}")
    mod_name = f"_bench_{kind}_{name}".replace("-", "_").replace(".", "_")
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    module_spec = importlib.util.spec_from_file_location(mod_name, path)
    module = importlib.util.module_from_spec(module_spec)
    sys.modules[mod_name] = module
    module_spec.loader.exec_module(module)
    return module
