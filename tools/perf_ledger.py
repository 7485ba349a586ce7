#!/usr/bin/env python
"""Cross-round perf ledger over the committed ``BENCH_r*.json`` history.

Usage::

    python tools/perf_ledger.py [root] [--threshold 0.10] [--json]
    python tools/perf_ledger.py --check-readme [root]

Every driver round commits one ``BENCH_r<NN>.json`` artifact
(``{"n", "cmd", "rc", "tail", "parsed": {...}|null}``).  Until now the
history was read by hand: nothing flagged a regression against a past
round, and README figures cited artifacts informally (the ADVICE r5 #3
failure mode — two with-valid numbers, no one could say which run
backed which).  This tool mechanizes both:

* **Trend table** — one row per round, one column per tracked
  throughput metric (headline 1M / full 10.5M legs, bin255, the two
  ranking legs, serve, with-valid), plus ``peak_hbm_bytes`` and the
  ``attribution_*`` fractions once rounds start carrying the
  device-time attribution leg.  Unparsed rounds (driver timeouts —
  r05's rc=124) stay visible as ``parse:null`` rows instead of
  silently vanishing from the history.

* **Regression flag** — the NEWEST parsed round is compared per metric
  against the BEST prior parsed round; any metric more than
  ``--threshold`` (default 10%) below its best prior exits nonzero and
  names the metric, the value, and the round that set the bar.  Only
  the newest round is judged: historical dips are history, not news.

* **README figure provenance** (``--check-readme``) — every throughput
  or ratio figure inside the README's fenced measured-run blocks must
  either carry an explicit not-captured marker (``no citable``,
  ``pending``, ``artifact lost``, ``projected``, ``not measured``) or
  name its source
  round (``BENCH_rNN``) — and the named artifact must actually contain
  a number within 15% of the claim.  This is the ratio-figure
  complement of tpulint's TPL008 (which can only check absolute
  ``M row-iters/s`` figures against the newest artifact): run as its
  own tier-1 gate (``tests/test_perf_ledger.py``).
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys
from typing import Any, Dict, List, Optional, Tuple

# tracked per-round metrics: (parsed key, short column label).  All
# higher-is-better throughputs/ratios — the regression rule below
# assumes that.
TRACKED: Tuple[Tuple[str, str], ...] = (
    ("value", "1M r-it/s"),
    ("full_row_iters_per_sec", "full r-it/s"),
    ("vs_baseline", "vs_base"),
    ("bin255_row_iters_per_sec", "bin255 r-it/s"),
    ("rank_doc_iters_per_sec", "rank d-it/s"),
    ("rank63_doc_iters_per_sec", "rank63 d-it/s"),
    ("serve_rows_per_sec", "serve rows/s"),
    ("valid_row_iters_per_sec", "valid r-it/s"),
    # fused multi-chip scan blocks (ISSUE 11): widest-mesh fused
    # row-iters/s and the fused-vs-per-iteration dispatch speedup,
    # derived from the leg's per-mesh-size multichip_table
    ("multichip_row_iters_per_sec", "mc r-it/s"),
    ("multichip_fused_speedup", "mc fused x"),
    # streamed out-of-core training at kernel speed (ISSUE 20): the
    # scale-phase streamed rows/s from the stream_ingest leg
    ("stream_rows_per_sec", "stream rows/s"),
)
ATTRIBUTION_KEYS = ("attribution_device_frac", "attribution_host_gap_frac",
                    "attribution_collective_frac")

_BENCH_RE = re.compile(r"BENCH_r(\d+)\.json")
_RATIO_RE = re.compile(r"(\d+(?:\.\d+)?)x\b")
_MFIG_RE = re.compile(r"(\d+(?:\.\d+)?)\s*M\s+(?:row|doc)-iters/s")
_ROUND_RE = re.compile(r"BENCH_r(\d+)")
UNCAPTURED_MARKERS = ("no citable", "pending", "artifact lost",
                      "projected", "uncaptured", "not measured")
FIGURE_TOLERANCE = 0.15


def load_history(root: str) -> List[Dict[str, Any]]:
    """Every BENCH_r*.json under ``root``, oldest first.  A file that
    fails to read/parse still lands in the history (``error`` field):
    the ledger must render what IS committed, not a survivor subset."""
    out = []
    try:
        names = sorted(n for n in os.listdir(root) if _BENCH_RE.fullmatch(n))
    except OSError:
        return out
    for name in names:
        entry: Dict[str, Any] = {
            "round": int(_BENCH_RE.fullmatch(name).group(1)), "file": name}
        try:
            with open(os.path.join(root, name), encoding="utf-8") as f:
                data = json.load(f)
            entry["rc"] = data.get("rc")
            p = data.get("parsed")
            if isinstance(p, dict):
                # flatten the multichip table's widest-mesh row into
                # the tracked flat keys (rows are per mesh size)
                rows = p.get("multichip_table")
                if isinstance(rows, list) and rows:
                    widest = max(rows, key=lambda r: r.get("devices", 0))
                    p = dict(p)
                    p.setdefault("multichip_row_iters_per_sec",
                                 widest.get("row_iters_per_sec"))
                    p.setdefault("multichip_fused_speedup",
                                 widest.get("fused_speedup"))
            entry["parsed"] = p if isinstance(p, dict) else None
        except (OSError, ValueError) as exc:
            entry["error"] = f"{type(exc).__name__}: {exc}"
            entry["parsed"] = None
        out.append(entry)
    return out


def check_regressions(history: List[Dict[str, Any]],
                      threshold: float = 0.10) -> List[Dict[str, Any]]:
    """Newest parsed round vs the best prior parsed round, per metric.
    A metric missing from the newest round is NOT a regression (legs
    get budget-skipped legitimately; the bench's own gates police
    that) — only a metric that RAN and came in low flags."""
    parsed = [h for h in history if h["parsed"]]
    if len(parsed) < 2:
        return []
    newest, priors = parsed[-1], parsed[:-1]
    out = []
    for key, label in TRACKED:
        now = newest["parsed"].get(key)
        if not isinstance(now, (int, float)) or isinstance(now, bool):
            continue
        best, best_round = None, None
        for h in priors:
            v = h["parsed"].get(key)
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                if best is None or v > best:
                    best, best_round = float(v), h["round"]
        if best is None or best <= 0:
            continue
        if float(now) < (1.0 - threshold) * best:
            out.append({"metric": key, "label": label,
                        "round": newest["round"], "value": float(now),
                        "best_prior": best, "best_round": best_round,
                        "ratio": round(float(now) / best, 4)})
    return out


def _fmt(v) -> str:
    if v is None:
        return "·"
    if isinstance(v, float) and abs(v) >= 1e5:
        return f"{v / 1e6:.1f}M"
    if isinstance(v, float):
        return f"{v:.3g}"
    return str(v)


def render_table(history: List[Dict[str, Any]], out=None) -> None:
    out = out if out is not None else sys.stdout   # late-bound: capsys
    p = lambda *a: print(*a, file=out)  # noqa: E731
    cols = [label for _, label in TRACKED]
    p(f"{'round':<7s} {'rc':>4s} " + " ".join(f"{c:>13s}" for c in cols)
      + f" {'peak_hbm':>10s}")
    p("-" * (13 + 14 * len(cols) + 11))
    best: Dict[str, float] = {}
    for h in history:
        parsed = h["parsed"]
        if parsed is None:
            reason = h.get("error", "parse:null (driver timeout class)")
            p(f"r{h['round']:<6d} {str(h.get('rc', '?')):>4s}  -- {reason}")
            continue
        cells = []
        for key, _ in TRACKED:
            v = parsed.get(key)
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                v = float(v)
                prev = best.get(key)
                mark = ""
                if prev is not None and prev > 0:
                    if v < 0.9 * prev:
                        mark = "!"      # >10% below the best prior round
                    elif v > prev:
                        mark = "+"
                best[key] = max(prev or 0.0, v)
                cells.append(f"{_fmt(v)}{mark:<1s}".rjust(13))
            else:
                cells.append(f"{'·':>13s}")
        peak = parsed.get("peak_hbm_bytes")
        peak_s = f"{peak / 2**30:.2f}G" if isinstance(peak, int) else "·"
        p(f"r{h['round']:<6d} {str(h.get('rc', '?')):>4s} "
          + " ".join(cells) + f" {peak_s:>10s}")
        attrs = {k: parsed[k] for k in ATTRIBUTION_KEYS if k in parsed}
        if attrs:
            p("        attribution: " + "  ".join(
                f"{k.replace('attribution_', '')}={parsed[k]}"
                for k in ATTRIBUTION_KEYS if k in parsed))
    p("\n(+ = new best for that metric; ! = >10% below the best prior "
      "round; · = not captured that round)")


# ---------------------------------------------------------------------------
# README figure provenance
# ---------------------------------------------------------------------------
def _numeric_leaves(obj, out: List[float]) -> None:
    if isinstance(obj, dict):
        for v in obj.values():
            _numeric_leaves(v, out)
    elif isinstance(obj, (list, tuple)):
        for v in obj:
            _numeric_leaves(v, out)
    elif isinstance(obj, (int, float)) and not isinstance(obj, bool):
        out.append(float(obj))


def _fenced_entries(lines: List[str]) -> List[Tuple[int, str]]:
    """(first_lineno, text) per fenced-block ENTRY: a ``label:`` line
    plus its indented continuation lines — figures and their source
    labels may sit on different physical lines of one entry."""
    entries: List[Tuple[int, str]] = []
    in_fence = False
    cur: Optional[Tuple[int, List[str]]] = None
    for lineno, line in enumerate(lines, 1):
        if line.lstrip().startswith("```"):
            if in_fence and cur:
                entries.append((cur[0], "\n".join(cur[1])))
            in_fence, cur = not in_fence, None
            continue
        if not in_fence:
            continue
        if line[:1].isspace() and cur is not None:
            cur[1].append(line)
        else:
            if cur:
                entries.append((cur[0], "\n".join(cur[1])))
            cur = (lineno, [line])
    if cur:
        entries.append((cur[0], "\n".join(cur[1])))
    return entries


def check_readme(root: str) -> List[str]:
    """Findings for README fenced-block figures that neither carry an
    explicit not-captured marker nor name a source round containing
    a matching number.  Empty list = provenance clean."""
    path = os.path.join(root, "README.md")
    try:
        with open(path, encoding="utf-8") as f:
            lines = f.read().splitlines()
    except OSError:
        return []
    artifacts = {h["round"]: h for h in load_history(root)}
    findings: List[str] = []
    for lineno, text in _fenced_entries(lines):
        low = text.lower()
        figures = ([("ratio", float(m)) for m in _RATIO_RE.findall(text)]
                   + [("mfig", float(m)) for m in _MFIG_RE.findall(text)])
        if not figures:
            continue
        if any(m in low for m in UNCAPTURED_MARKERS):
            continue
        rounds = [int(r) for r in _ROUND_RE.findall(text)]
        if not rounds:
            findings.append(
                f"README.md:{lineno}: measured figure(s) "
                f"{[f'{v}' for _, v in figures]} cite no source round — "
                f"add '(BENCH_rNN)' or an explicit not-captured marker")
            continue
        leaves: List[float] = []
        for r in rounds:
            h = artifacts.get(r)
            if h is None or h["parsed"] is None:
                findings.append(
                    f"README.md:{lineno}: cites BENCH_r{r:02d} but that "
                    f"artifact is missing or unparsed")
            else:
                _numeric_leaves(h["parsed"], leaves)
        if not leaves:
            continue
        for kind, claimed in figures:
            cands = [claimed] if kind == "ratio" else [claimed * 1e6]
            if kind == "mfig":
                cands.append(claimed)   # some keys record M directly
            ok = any(abs(c - v) <= FIGURE_TOLERANCE * max(abs(v), 1e-9)
                     for c in cands for v in leaves)
            if not ok:
                findings.append(
                    f"README.md:{lineno}: figure {claimed}"
                    f"{'x' if kind == 'ratio' else 'M'} not found within "
                    f"{int(FIGURE_TOLERANCE * 100)}% in cited round(s) "
                    f"{rounds} — re-measure or relabel with its real "
                    f"source run")
    return findings


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("root", nargs="?", default=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    ap.add_argument("--threshold", type=float, default=0.10,
                    help="regression flag threshold vs best prior round")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable output")
    ap.add_argument("--check-readme", action="store_true",
                    help="check README fenced figures name source rounds")
    args = ap.parse_args(argv)
    history = load_history(args.root)
    if args.check_readme:
        findings = check_readme(args.root)
        for f in findings:
            print(f)
        if not findings:
            print("README figure provenance: clean")
        return 1 if findings else 0
    if not history:
        print(f"no BENCH_r*.json under {args.root}", file=sys.stderr)
        return 2
    regressions = check_regressions(history, args.threshold)
    if args.json:
        print(json.dumps({"history": history, "regressions": regressions},
                         indent=1))
    else:
        render_table(history)
        for r in regressions:
            print(f"REGRESSION: {r['metric']} r{r['round']:02d} = "
                  f"{_fmt(r['value'])} is {100 * (1 - r['ratio']):.1f}% "
                  f"below best prior r{r['best_round']:02d} = "
                  f"{_fmt(r['best_prior'])}")
    return 1 if regressions else 0


if __name__ == "__main__":
    sys.exit(main())
