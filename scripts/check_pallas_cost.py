#!/usr/bin/env python
"""Static lint: every `pallas_call` in the package must carry a
`cost_estimate` (ISSUE 9 satellite; tier-1 via
tests/test_fused_optimizer.py).

XLA's HLO cost analysis cannot see inside a Pallas custom call — a
Mosaic kernel reports ~0 FLOPs/bytes — so XLA's scheduler and the
serving accountant (`observability/roofline.py`) depend on each kernel
declaring its analytic cost via
`pl.CostEstimate(flops=..., bytes_accessed=..., ...)`. A kernel shipped
without one silently blinds both for every program that embeds it; this
lint turns that into a CI failure instead.

Checked statically over the whole `analytics_zoo_tpu/` package: each
`pallas_call(` call expression (nested parens respected, multi-line
included) must contain a `cost_estimate=` keyword. A call may opt out
with a trailing `# pallas-cost-ok: <reason>` comment on the
`pallas_call(` line; the reason is mandatory so the waiver documents
itself.

    python scripts/check_pallas_cost.py [repo_root]
"""

from __future__ import annotations

import os
import re
import sys
from typing import List

PKG = "analytics_zoo_tpu"

# no \s* before the paren: prose like "pallas_call (Mosaic reports ~0)"
# in docstrings/comments must not match
CALL_RE = re.compile(r"\bpallas_call\(")
ALLOW_RE = re.compile(r"#\s*pallas-cost-ok:\s*\S")
COST_RE = re.compile(r"\bcost_estimate\s*=")


def _call_slice(src: str, open_paren: int) -> str:
    """The argument text of the call whose '(' sits at `open_paren`,
    respecting nested parens/brackets (multi-line calls included)."""
    depth = 0
    for i in range(open_paren, len(src)):
        c = src[i]
        if c in "([{":
            depth += 1
        elif c in ")]}":
            depth -= 1
            if depth == 0:
                return src[open_paren + 1:i]
    return src[open_paren + 1:]


def _line_of(src: str, pos: int) -> int:
    return src.count("\n", 0, pos) + 1


def _line_text(src: str, pos: int) -> str:
    start = src.rfind("\n", 0, pos) + 1
    end = src.find("\n", pos)
    return src[start:end if end != -1 else len(src)]


def check_file(path: str) -> List[str]:
    with open(path, encoding="utf-8") as fh:
        src = fh.read()
    errors = []
    for m in CALL_RE.finditer(src):
        # the returned transform is CALLED with operands right after
        # `pallas_call(...)` — the kwargs live in the FIRST paren group
        args = _call_slice(src, m.end() - 1)
        if COST_RE.search(args):
            continue
        if ALLOW_RE.search(_line_text(src, m.start())):
            continue
        errors.append(
            f"{path}:{_line_of(src, m.start())}: pallas_call without a "
            "cost_estimate= (roofline gauges go blind for any program "
            "embedding this kernel; add pl.CostEstimate(...) or a "
            "'# pallas-cost-ok: <reason>' waiver)")
    return errors


# Presence manifest (ISSUE 19 satellite): kernels the roofline layer
# KNOWS about must keep at least this many costed `pallas_call` sites
# in place — decode_attention carries TWO (the contiguous decode-step
# kernel and the paged block-table kernel), so a refactor that drops
# one (or moves it somewhere the analytic cost no longer reaches)
# fails CI instead of silently zeroing that kernel's roofline bytes.
EXPECTED_MIN_CALLS = {
    os.path.join("pallas", "decode_attention.py"): 2,
    os.path.join("pallas", "flash_attention.py"): 1,
    os.path.join("pallas", "fused_adam.py"): 1,
    os.path.join("pallas", "dropout.py"): 1,
    os.path.join("pallas", "segment_update.py"): 1,
}


def check(root: str = ".") -> List[str]:
    errors: List[str] = []
    pkg = os.path.join(root, PKG)
    counts = {}
    for dirpath, dirnames, filenames in os.walk(pkg):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for name in sorted(filenames):
            if name.endswith(".py"):
                path = os.path.join(dirpath, name)
                errors.extend(check_file(path))
                with open(path, encoding="utf-8") as fh:
                    counts[os.path.relpath(path, pkg)] = len(
                        CALL_RE.findall(fh.read()))
    for rel, want in sorted(EXPECTED_MIN_CALLS.items()):
        have = counts.get(rel, 0)
        if have < want:
            errors.append(
                f"{os.path.join(pkg, rel)}: expected >= {want} "
                f"pallas_call site(s), found {have} (a known kernel "
                "went missing — update EXPECTED_MIN_CALLS if this is "
                "an intentional removal)")
    return errors


def main(argv: List[str]) -> int:
    root = argv[1] if len(argv) > 1 else "."
    errors = check(root)
    for e in errors:
        print(e)
    if errors:
        print(f"{len(errors)} pallas_call(s) without cost_estimate")
        return 1
    print("pallas cost-estimate lint: clean")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
