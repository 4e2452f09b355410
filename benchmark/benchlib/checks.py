"""The comparisons that decide `correct`: a run is correct when every
check a job added passed."""

from __future__ import annotations

import sys
from typing import Any, Dict


class Checks(Dict[str, Dict[str, Any]]):
    def add(self, name: str, ok: bool, detail: Any) -> None:
        self[name] = {"ok": bool(ok), "detail": detail}
        if not ok:
            print(f"[bench] CHECK FAILED {name}: {detail}", file=sys.stderr,
                  flush=True)

    @property
    def all_ok(self) -> bool:
        return all(c["ok"] for c in self.values())
