"""Cross-round perf ledger tests (tools/perf_ledger.py, ISSUE 10).

Unit half: synthetic BENCH histories prove the regression flag (>10%
below the best prior round exits nonzero, naming metric and rounds)
and the README figure-provenance rules.  Integration half: the ledger
must render a trend row for EVERY BENCH_r*.json of a history (unparsed
driver-timeout rounds included), hold on an EMPTY committed history,
and the repo README's fenced measured figures must name source rounds
that actually contain them or say they were not measured — the
mechanized TPL008 companion for ratio figures (ADVICE r5 #3).
"""
import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from tools.perf_ledger import (check_readme, check_regressions,  # noqa: E402
                               load_history, main, render_table)


def _write(root, n, parsed, rc=0):
    with open(os.path.join(root, f"BENCH_r{n:02d}.json"), "w") as f:
        json.dump({"n": n, "cmd": "bench", "rc": rc, "tail": "",
                   "parsed": parsed}, f)


# ---------------------------------------------------------------------------
# regression flag on synthetic history
# ---------------------------------------------------------------------------
def test_injected_regression_flags_and_exits_nonzero(tmp_path, capsys):
    root = str(tmp_path)
    _write(root, 1, {"value": 10e6, "full_row_iters_per_sec": 20e6,
                     "vs_baseline": 1.0})
    # value regresses 15% (> the 10% threshold); full improves
    _write(root, 2, {"value": 8.5e6, "full_row_iters_per_sec": 22e6,
                     "vs_baseline": 1.1})
    regs = check_regressions(load_history(root))
    assert len(regs) == 1
    r = regs[0]
    assert r["metric"] == "value" and r["round"] == 2
    assert r["best_round"] == 1 and r["ratio"] == pytest.approx(0.85)
    assert main([root]) == 1
    out = capsys.readouterr().out
    assert "REGRESSION" in out and "value" in out


def test_clean_history_exits_zero(tmp_path, capsys):
    root = str(tmp_path)
    _write(root, 1, {"value": 10e6})
    _write(root, 2, {"value": 10.5e6})
    assert check_regressions(load_history(root)) == []
    assert main([root]) == 0


def test_regression_judges_only_newest_parsed_round(tmp_path):
    root = str(tmp_path)
    _write(root, 1, {"value": 10e6})
    _write(root, 2, {"value": 5e6})     # historical dip...
    _write(root, 3, {"value": 11e6})    # ...recovered: not news
    _write(root, 4, None, rc=124)       # newest is unparsed -> r3 judged
    assert check_regressions(load_history(root)) == []


def test_missing_metric_is_not_a_regression(tmp_path):
    """A budget-skipped leg (metric absent from the newest round) must
    not flag — the bench's own gates police skipped legs."""
    root = str(tmp_path)
    _write(root, 1, {"value": 10e6, "serve_rows_per_sec": 1e6})
    _write(root, 2, {"value": 10.2e6})
    assert check_regressions(load_history(root)) == []


def test_unparsed_rounds_stay_visible(tmp_path, capsys):
    root = str(tmp_path)
    _write(root, 1, {"value": 10e6})
    _write(root, 2, None, rc=124)
    hist = load_history(root)
    assert [h["round"] for h in hist] == [1, 2]
    assert hist[1]["parsed"] is None
    render_table(hist)
    out = capsys.readouterr().out
    assert "r2" in out and "parse:null" in out


# ---------------------------------------------------------------------------
# README figure provenance
# ---------------------------------------------------------------------------
def _readme(root, body):
    with open(os.path.join(root, "README.md"), "w") as f:
        f.write(body)


def test_readme_figure_without_source_round_flags(tmp_path):
    root = str(tmp_path)
    _write(root, 1, {"value": 36.5e6})
    _readme(root, "intro\n```\nleg:  36.5M row-iters/s (1.66x)\n```\n")
    findings = check_readme(root)
    assert len(findings) == 1 and "cite no source round" in findings[0]


def test_readme_figure_with_matching_round_is_clean(tmp_path):
    root = str(tmp_path)
    _write(root, 4, {"value": 36.5e6, "vs_baseline": 1.66})
    _readme(root, "```\nleg:  36.5M row-iters/s (1.66x, BENCH_r04)\n```\n")
    assert check_readme(root) == []


def test_readme_mismatched_figure_flags(tmp_path):
    root = str(tmp_path)
    _write(root, 4, {"value": 36.5e6, "vs_baseline": 1.66})
    # claims 2x what the cited artifact records
    _readme(root, "```\nleg:  70.0M row-iters/s (BENCH_r04)\n```\n")
    findings = check_readme(root)
    assert len(findings) == 1 and "not found within" in findings[0]


def test_readme_uncaptured_markers_skip(tmp_path):
    root = str(tmp_path)
    _write(root, 1, {"value": 1e6})
    _readme(root, "```\nleg:  0.27x — round-5 session, artifact lost\n"
                  "other: 3.0x projected from arithmetic\n```\n")
    assert check_readme(root) == []


def test_readme_prose_figures_ignored(tmp_path):
    """Only fenced measured-run blocks are claims; prose arithmetic
    (targets, baselines) is not checked — same scope rule as TPL008."""
    root = str(tmp_path)
    _write(root, 1, {"value": 1e6})
    _readme(root, "The target is 3.0x the 22.0M row-iters/s baseline.\n")
    assert check_readme(root) == []


def test_readme_entry_groups_continuation_lines(tmp_path):
    """A figure and its (BENCH_rNN) label may sit on different lines of
    one entry (label line + indented continuations)."""
    root = str(tmp_path)
    _write(root, 4, {"value": 36.5e6, "vs_baseline": 1.66})
    _readme(root, "```\nleg:   36.5M row-iters/s measured\n"
                  "       (1.66x the baseline; BENCH_r04)\n```\n")
    assert check_readme(root) == []


# ---------------------------------------------------------------------------
# a five-round history shaped like a driver's (fixture artifacts), and
# the COMMITTED repo history + README (tier-1 gates).  The committed
# history is empty since PR 21 (the old rounds were runs of a backend
# that no longer exists): every gate must hold on no artifact at all.
# ---------------------------------------------------------------------------
def _five_rounds(root):
    _write(root, 1, {"value": 1e6})
    _write(root, 2, {"value": 2e6})
    _write(root, 3, {"value": 3e6})
    _write(root, 4, {"value": 4e6, "full_row_iters_per_sec": 5e6})
    _write(root, 5, None, rc=124)       # driver-timeout round


def test_history_renders_every_round(tmp_path, capsys):
    root = str(tmp_path)
    _five_rounds(root)
    hist = load_history(root)
    assert [h["round"] for h in hist] == [1, 2, 3, 4, 5]
    # r5 is an rc=124 driver-timeout artifact: visible, unparsed
    r5 = next(h for h in hist if h["round"] == 5)
    assert r5["parsed"] is None and r5["rc"] == 124
    render_table(hist)
    out = capsys.readouterr().out
    for r in ("r1", "r2", "r3", "r4", "r5"):
        assert r in out
    assert "parse:null" in out


def test_committed_history_has_no_regression(tmp_path):
    """The newest parsed round must sit within 10% of every metric's
    best prior round — the standing cross-round perf gate, which an
    empty committed history passes and a five-round fixture history
    with a monotone headline passes too."""
    assert check_regressions(load_history(REPO)) == []
    _five_rounds(str(tmp_path))
    assert check_regressions(load_history(str(tmp_path))) == []


def test_repo_readme_figures_name_source_rounds():
    """Every measured figure in the README's fenced blocks names a
    source round that contains it (or carries an explicit
    not-captured marker) — ADVICE r5 #3, mechanized."""
    assert check_readme(REPO) == []


def test_readme_not_measured_marker_skips(tmp_path):
    """With no artifact at all, a fenced figure marked `not measured`
    is clean and an unmarked one still flags."""
    root = str(tmp_path)
    _readme(root, "```\nleg:  not measured on the current tree "
                  "(target 3.0x)\n```\n")
    assert check_readme(root) == []
    _readme(root, "```\nleg:  36.5M row-iters/s (1.66x)\n```\n")
    assert len(check_readme(root)) == 1
