"""The comparisons that decide `correct`: a run is correct when every
check a job added passed."""

from __future__ import annotations

import re
import sys
from typing import Any, Dict, List


class Checks(Dict[str, Dict[str, Any]]):
    def add(self, name: str, ok: bool, detail: Any) -> None:
        self[name] = {"ok": bool(ok), "detail": detail}
        if not ok:
            print(f"[bench] CHECK FAILED {name}: {detail}", file=sys.stderr,
                  flush=True)

    @property
    def all_ok(self) -> bool:
        return all(c["ok"] for c in self.values())


def custom_call_names(hlo: str) -> List[str]:
    """The pallas calls of a compiled program's text, by instruction."""
    return re.findall(
        r'%([\w.\-]+) = [^\n]*custom_call_target="tpu_custom_call"', hlo)


def kernel_calls(hlo: str, patterns: Dict[str, str]) -> Dict[str, int]:
    """How many pallas calls of the compiled step each pattern names."""
    names = custom_call_names(hlo)
    return {key: sum(bool(re.match(pattern, name)) for name in names)
            for key, pattern in patterns.items()}


def attention_as_expected(impl: str, want: str,
                          calls: Dict[str, int]) -> bool:
    """What a user needs of the attention the step compiled: the
    implementation the configuration expects and, for `flash`, a kernel on
    the way forward (`fwd`) and one on the way back that makes dK and dV
    (`bwd_dkv`). A separate `bwd_dq` call is reported, not required: a
    fused backward makes dQ in the `bwd_dkv` call."""
    return impl == want and (want != "flash" or (
        calls.get("fwd", 0) > 0 and calls.get("bwd_dkv", 0) > 0))


def grouped_matmul_as_expected(impl: str, want: str,
                               calls: Dict[str, int]) -> bool:
    """The grouped matmul the configuration expects and, for `megablox`,
    of each of the expert FFN's two matmuls at least the forward and the
    transpose for the rows (`gmm` >= 4) and the transpose for the weights
    (`tgmm` >= 2). Whether a forward runs again under remat is the
    program's to decide."""
    return impl == want and (want != "megablox" or (
        calls.get("gmm", 0) >= 4 and calls.get("tgmm", 0) >= 2))
