"""Tier-1 gate: detcheck determinism & numerics analysis.

Mirrors the tpulint/spmdcheck/memcheck gate layers:

1. **Package gate** — ``lightgbm_tpu/`` must analyze clean against the
   committed baseline (``tools/detcheck/baseline.json``, EMPTY), via
   the shared umbrella run (``tools.check.cached_run_all``: one AST
   parse serves all four static gates in a pytest session).
2. **Rule correctness** — fixtures under ``detcheck_fixtures/`` carry
   ``# EXPECT: DETxxx`` markers; the analyzer must report EXACTLY the
   marked (line, rule) pairs.
3. **Seeded hazards** — the acceptance patterns (ISSUE 12): the
   pre-fix DART shape (a ``RandomState`` stored on an instance) seeded
   back into a copy of ``variants.py`` fails the gate with DET001 at
   the right file:line, and a NEW env-gated program seam seeded into
   ``gbdt.py`` fails with DET005.
4. **Registry plumbing** — every registered parity gate / tie-break
   test exists, the seam/exempt tables don't overlap, and the two
   pre-existing DET001 findings this PR fixed (``variants.py:34``,
   ``engine.py:282``) stay fixed (no RandomState reappears there).
"""
import os
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO, "tests", "detcheck_fixtures")

from tools.analysis_core import assert_fixtures_match  # noqa: E402
from tools.detcheck import (BASELINE_DEFAULT, load_baseline,  # noqa: E402
                            new_findings, run_detcheck, write_baseline)


# ---------------------------------------------------------------------------
# 1. package gate (through the shared umbrella run)
# ---------------------------------------------------------------------------
def test_package_clean_vs_baseline():
    from tools.check import cached_run_all
    _, fresh = cached_run_all(REPO)["detcheck"]
    assert not fresh, ("new detcheck findings (fix, suppress with "
                       "justification, or --update-baseline):\n"
                       + "\n".join(f.render() for f in fresh))


def test_committed_baseline_is_empty():
    baseline = load_baseline(os.path.join(REPO, BASELINE_DEFAULT))
    assert baseline == {}, ("the detcheck baseline must stay EMPTY — "
                            "fix or justify-suppress instead of pinning: "
                            f"{baseline}")


# ---------------------------------------------------------------------------
# 2. rule correctness on fixtures
# ---------------------------------------------------------------------------
def test_fixtures_match_expect_markers():
    findings, _ = run_detcheck([FIXTURES], root=REPO,
                               project_rules=False)
    checked = assert_fixtures_match(FIXTURES, findings)
    assert checked >= 12    # pos+neg per rule


def test_suppression_clears_finding(tmp_path):
    mod = tmp_path / "mod.py"
    mod.write_text(
        "import numpy as np\n\n\n"
        "def jitter(scale):\n"
        "    # detcheck: disable=DET001 -- decorrelates retries only\n"
        "    return scale * np.random.rand()\n")
    findings, _ = run_detcheck(["mod.py"], root=str(tmp_path),
                               project_rules=False)
    assert not findings, [f.render() for f in findings]


def test_baseline_roundtrip(tmp_path):
    mod = tmp_path / "mod.py"
    shutil.copy(os.path.join(FIXTURES, "det002_pos.py"), mod)
    findings, by_rel = run_detcheck(["mod.py"], root=str(tmp_path),
                                    project_rules=False)
    assert findings
    bl_path = tmp_path / "baseline.json"
    write_baseline(str(bl_path), findings, by_rel)
    again, by_rel2 = run_detcheck(["mod.py"], root=str(tmp_path),
                                  project_rules=False)
    assert not new_findings(again, by_rel2, load_baseline(str(bl_path)))
    # a NEW hazard (distinct line text) surfaces through the pin
    mod.write_text(mod.read_text() + (
        "\n\ndef fresh_hazard(seed, n):\n"
        "    key = jax.random.PRNGKey(seed)\n"
        "    a = jax.random.uniform(key, (n,))\n"
        "    b = jax.random.bernoulli(key, 0.5, (n,))\n"
        "    return a, b\n"))
    third, by_rel3 = run_detcheck(["mod.py"], root=str(tmp_path),
                                  project_rules=False)
    fresh = new_findings(third, by_rel3, load_baseline(str(bl_path)))
    assert len(fresh) == 1 and fresh[0].rule == "DET002", \
        [f.render() for f in fresh]


# ---------------------------------------------------------------------------
# 3. seeded hazards (the acceptance patterns)
# ---------------------------------------------------------------------------
DET001_SEED = (
    "\n\nclass _DetProbeBooster:\n"
    "    def __init__(self, seed):\n"
    "        self._rng_probe = np.random.RandomState(seed)\n\n"
    "    def draw(self):\n"
    "        return self._rng_probe.rand()\n")

DET005_SEED = (
    "\n\ndef _det_probe_fast_path():\n"
    "    return _os.environ.get(\"LGBM_TPU_DET_PROBE\", \"1\") != \"0\"\n")


def _seed_package(tmp_path, rel, seed_text, marker):
    pkg = tmp_path / "lightgbm_tpu"
    shutil.copytree(os.path.join(REPO, "lightgbm_tpu"), pkg,
                    ignore=shutil.ignore_patterns("__pycache__"))
    target = pkg / rel
    target.write_text(target.read_text() + seed_text)
    lines = target.read_text().splitlines()
    return [i + 1 for i, ln in enumerate(lines) if marker in ln][-1]


def test_seeded_stateful_rng_fails_gate(tmp_path):
    """Acceptance: the pre-migration DART shape — a RandomState stored
    on an instance attribute — seeded back into a copy of variants.py
    fails the gate with DET001 and the correct file:line."""
    hazard_line = _seed_package(
        tmp_path, os.path.join("boosting", "variants.py"), DET001_SEED,
        "self._rng_probe = np.random.RandomState(seed)")
    findings, by_rel = run_detcheck(["lightgbm_tpu"], root=str(tmp_path))
    baseline = load_baseline(os.path.join(REPO, BASELINE_DEFAULT))
    fresh = new_findings(findings, by_rel, baseline)
    assert any(f.rule == "DET001"
               and f.file == "lightgbm_tpu/boosting/variants.py"
               and f.line == hazard_line for f in fresh), \
        [f.render() for f in fresh]

    # ... and the CLI exits non-zero printing file:line + rule id
    proc = subprocess.run(
        [sys.executable, "-m", "tools.detcheck", "--root", str(tmp_path),
         "lightgbm_tpu"],
        cwd=REPO, capture_output=True, text=True)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert (f"lightgbm_tpu/boosting/variants.py:{hazard_line}: DET001"
            in proc.stdout), proc.stdout


def test_seeded_unregistered_seam_fails_gate(tmp_path):
    """Acceptance: a NEW env-flag program seam (no PROGRAM_PAIRS entry,
    no exemption) seeded into gbdt.py fails the gate with DET005 at the
    env-read line — a dual-path seam cannot land without naming its
    parity gate."""
    hazard_line = _seed_package(
        tmp_path, os.path.join("boosting", "gbdt.py"), DET005_SEED,
        "LGBM_TPU_DET_PROBE")
    findings, by_rel = run_detcheck(["lightgbm_tpu"], root=str(tmp_path))
    baseline = load_baseline(os.path.join(REPO, BASELINE_DEFAULT))
    fresh = new_findings(findings, by_rel, baseline)
    assert any(f.rule == "DET005"
               and f.file == "lightgbm_tpu/boosting/gbdt.py"
               and f.line == hazard_line for f in fresh), \
        [f.render() for f in fresh]

    proc = subprocess.run(
        [sys.executable, "-m", "tools.detcheck", "--root", str(tmp_path),
         "lightgbm_tpu"],
        cwd=REPO, capture_output=True, text=True)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert (f"lightgbm_tpu/boosting/gbdt.py:{hazard_line}: DET005"
            in proc.stdout), proc.stdout


# ---------------------------------------------------------------------------
# 4. registry plumbing + the fixed findings stay fixed
# ---------------------------------------------------------------------------
def test_registry_tests_exist():
    from tools.detcheck import parity_registry as reg
    for entry in reg.PROGRAM_PAIRS:
        assert reg.test_exists(entry["test"]), (
            f"PROGRAM_PAIRS `{entry['name']}` names missing test "
            f"{entry['test']}")
    for rel, entry in reg.TIE_BREAK.items():
        if "exempt" not in entry:
            assert reg.test_exists(entry["test"]), (rel, entry)
    assert not (set(reg.EXEMPT_ENV)
                & {e["env"] for e in reg.PROGRAM_PAIRS})


def test_registry_covers_known_seams():
    """The load-bearing seams this repo actually ships must be
    registered (a refactor that drops one regresses the contract)."""
    from tools.detcheck import parity_registry as reg
    envs = {e["env"] for e in reg.PROGRAM_PAIRS}
    assert {"LGBM_TPU_MESH_BLOCK", "LGBM_TPU_SPLIT_CACHE",
            "LGBM_TPU_DONATE", "LGBM_TPU_DART_HOST_RNG"} <= envs
    assert "lightgbm_tpu/ops/split.py" in reg.TIE_BREAK


def test_preexisting_det001_findings_stay_fixed():
    """ISSUE 12 acceptance: variants.py and engine.py carry NO
    RandomState-based derivations anymore (fixed, not baselined) —
    outside the documented DART escape hatch, which must carry its
    inline justification."""
    var = open(os.path.join(REPO, "lightgbm_tpu", "boosting",
                            "variants.py")).read()
    eng = open(os.path.join(REPO, "lightgbm_tpu", "engine.py")).read()
    assert "np.random.RandomState(" not in eng
    # the only RandomState CONSTRUCTION left in variants.py is the
    # justified escape hatch
    lines = [ln for ln in var.splitlines()
             if "np.random.RandomState(" in ln
             and not ln.strip().startswith("#")]
    assert len(lines) == 1 and "_rng_drop" in lines[0], lines
    assert "detcheck: disable=DET001" in var
