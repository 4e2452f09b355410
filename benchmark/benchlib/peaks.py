"""Published peaks, keyed by `device_kind`. An unknown kind is an error."""

from __future__ import annotations

import json
import os
from typing import Any, Dict

_PATH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "peaks.json")


def peaks_for(device_kind: str) -> Dict[str, Any]:
    with open(_PATH) as f:
        table = json.load(f)
    entry = table.get(device_kind)
    if not isinstance(entry, dict):
        known = sorted(k for k, v in table.items() if isinstance(v, dict))
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r} in "
            f"{_PATH} (known: {known}); add a row with its source, "
            f"never a default")
    return entry
