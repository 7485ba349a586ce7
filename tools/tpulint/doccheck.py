"""TPL008: README perf figures vs the latest BENCH_r*.json artifact.

ADVICE r5 item 3 flagged the failure mode by hand: the README quoted
two different with-valid slowdowns and nobody could say which artifact
backed which.  This check mechanizes the detectable slice of that
class: every throughput figure the README quotes as measured
(``NN.N M row-iters/s``) must sit within tolerance of SOME throughput
recorded in the newest parsed ``BENCH_r*.json`` (``value`` /
``full_row_iters_per_sec``).  The tolerance is 15% (the run-to-run
spread on a local chip is not measured yet) — the gate catches stale
orders-of-magnitude claims after a perf change, not jitter.

Artifacts whose ``parsed`` is null (driver timeout runs) are skipped;
no parsed artifact at all -> no findings (nothing authoritative to
check against).
"""
from __future__ import annotations

import json
import os
import re
from typing import List

from .core import Finding

_FIGURE_RE = re.compile(r"(\d+(?:\.\d+)?)\s*M\s+row-iters/s")
_TOLERANCE = 0.15


def _latest_bench_throughputs(root: str) -> List[float]:
    """Throughput figures (in M row-iters/s) from the newest BENCH
    artifact that actually parsed."""
    try:
        names = sorted(n for n in os.listdir(root)
                       if re.fullmatch(r"BENCH_r\d+\.json", n))
    except OSError:
        return []
    for name in reversed(names):
        try:
            with open(os.path.join(root, name), encoding="utf-8") as f:
                data = json.load(f)
        except (OSError, ValueError):
            continue
        parsed = data.get("parsed")
        if not isinstance(parsed, dict):
            continue
        vals = [parsed.get(k) for k in ("value", "full_row_iters_per_sec")]
        out = [float(v) / 1e6 for v in vals if isinstance(v, (int, float))]
        if out:
            return out
    return []


def rule_tpl008(root: str) -> List[Finding]:
    readme = os.path.join(root, "README.md")
    try:
        with open(readme, encoding="utf-8") as f:
            lines = f.read().splitlines()
    except OSError:
        return []
    bench = _latest_bench_throughputs(root)
    if not bench:
        return []
    out: List[Finding] = []
    in_fence = False
    for lineno, line in enumerate(lines, 1):
        if line.lstrip().startswith("```"):
            in_fence = not in_fence
            continue
        if not in_fence:
            # prose mentions the CPU baseline / target arithmetic by the
            # same unit; only fenced measured-run blocks are claims the
            # artifact must back
            continue
        for m in _FIGURE_RE.finditer(line):
            claimed = float(m.group(1))
            if any(abs(claimed - b) <= _TOLERANCE * b for b in bench):
                continue
            nearest = min(bench, key=lambda b: abs(claimed - b))
            out.append(Finding(
                "README.md", lineno, "TPL008",
                f"README claims {claimed}M row-iters/s but the latest "
                f"parsed BENCH artifact records "
                f"{', '.join(f'{b:.1f}M' for b in bench)} (nearest "
                f"{nearest:.1f}M, >15% off): re-measure or relabel the "
                f"figure with its source run"))
    return out
