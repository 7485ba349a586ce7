"""Tier-1 gate: numcheck numeric-reproducibility discipline.

Mirrors the tpulint/spmdcheck/memcheck/detcheck/concheck gate layers:

1. **Package gate** — ``lightgbm_tpu/`` + ``tests/`` must analyze
   clean against the committed baseline
   (``tools/numcheck/baseline.json``, EMPTY), via the shared umbrella
   run (``tools.check.cached_run_all``: one AST parse serves all six
   static gates in a pytest session).
2. **Rule correctness** — fixtures under ``numcheck_fixtures/`` carry
   ``# EXPECT: NUMxxx`` markers; the analyzer must report EXACTLY the
   marked (line, rule) pairs.
3. **Seeded hazard** — the acceptance pattern (ISSUE 19): a raw
   ``jnp.sum(grad * bag)`` root reduction seeded into a copy of
   ``learner/serial.py`` — the literal PR 14 bug — fails the gate
   with NUM001 at the right file:line, through both the library API
   and the CLI.
4. **Registry coherence** — the static registry, the runtime ulp
   contract (``obs/num_contract.py``), and the measured envelope
   (``parallel/envelope.py``) share budgets BY NAME; and every
   reducer-migration helper is bitwise-identical to the raw
   expression it replaced (the migration must be a no-op on bytes).
"""
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO, "tests", "numcheck_fixtures")

from tools.analysis_core import assert_fixtures_match  # noqa: E402
from tools.numcheck import (BASELINE_DEFAULT, load_baseline,  # noqa: E402
                            new_findings, run_numcheck, write_baseline)
from tools.numcheck import reduction_registry as reg  # noqa: E402
from tools.numcheck.tolerance_registry import TOLERANCES, tol  # noqa: E402


# ---------------------------------------------------------------------------
# 1. package gate (through the shared umbrella run)
# ---------------------------------------------------------------------------
def test_package_clean_vs_baseline():
    from tools.check import cached_run_all
    _, fresh = cached_run_all(REPO)["numcheck"]
    assert not fresh, ("new numcheck findings (fix, suppress with "
                       "justification, or --update-baseline):\n"
                       + "\n".join(f.render() for f in fresh))


def test_committed_baseline_is_empty():
    baseline = load_baseline(os.path.join(REPO, BASELINE_DEFAULT))
    assert baseline == {}, ("the numcheck baseline must stay EMPTY — "
                            "fix or justify-suppress instead of pinning: "
                            f"{baseline}")


# ---------------------------------------------------------------------------
# 2. rule correctness on fixtures
# ---------------------------------------------------------------------------
def test_fixtures_match_expect_markers():
    findings, _ = run_numcheck([FIXTURES], root=FIXTURES,
                               project_rules=False)
    checked = assert_fixtures_match(FIXTURES, findings)
    assert checked >= 10    # pos+neg per rule NUM001-NUM005


def test_suppression_clears_finding(tmp_path):
    mod = tmp_path / "mod.py"
    mod.write_text(
        "import jax.numpy as jnp\n\n\n"
        "def _root(grad, bag):\n"
        "    # numcheck: disable=NUM001 -- toy: proving the disable\n"
        "    # syntax covers the next source line\n"
        "    return jnp.sum(grad * bag)\n")
    findings, _ = run_numcheck(["mod.py"], root=str(tmp_path),
                               project_rules=False)
    assert not findings, [f.render() for f in findings]


def test_unjustified_suppression_is_recorded(tmp_path):
    """A disable with no '-- why' suppresses (the chassis contract)
    but lands in FileInfo.unjustified — tpulint's TPL000 turns that
    into a finding in the umbrella run, for every analyzer's tags."""
    mod = tmp_path / "mod.py"
    mod.write_text(
        "import jax.numpy as jnp\n\n\n"
        "def _root(grad, bag):\n"
        "    return jnp.sum(grad * bag)  # numcheck: disable=NUM001\n")
    findings, by_rel = run_numcheck(["mod.py"], root=str(tmp_path),
                                    project_rules=False)
    assert not findings, [f.render() for f in findings]
    assert by_rel["mod.py"].unjustified == [5]


def test_baseline_roundtrip(tmp_path):
    mod = tmp_path / "mod.py"
    shutil.copy(os.path.join(FIXTURES, "num001_pos.py"), mod)
    findings, by_rel = run_numcheck(["mod.py"], root=str(tmp_path),
                                    project_rules=False)
    assert findings
    bl_path = tmp_path / "baseline.json"
    write_baseline(str(bl_path), findings, by_rel)
    again, by_rel2 = run_numcheck(["mod.py"], root=str(tmp_path),
                                  project_rules=False)
    assert not new_findings(again, by_rel2, load_baseline(str(bl_path)))
    # a NEW hazard (distinct line text) surfaces through the pin
    mod.write_text(mod.read_text() + (
        "\n\ndef _n1p_fresh_hazard(hess):\n"
        "    return jnp.sum(hess * hess)\n"))
    third, by_rel3 = run_numcheck(["mod.py"], root=str(tmp_path),
                                  project_rules=False)
    fresh = new_findings(third, by_rel3, load_baseline(str(bl_path)))
    assert len(fresh) == 1 and fresh[0].rule == "NUM001", \
        [f.render() for f in fresh]


# ---------------------------------------------------------------------------
# 3. seeded hazard (the acceptance pattern)
# ---------------------------------------------------------------------------
# The literal PR 14 bug, reintroduced: raw reassociable root
# reductions over grad/hess OUTSIDE the registered root_stats family.
NUM001_SEED = (
    "\n\ndef _num_probe_root(grad, hess, bag):\n"
    "    sg = jnp.sum(grad * bag)  # numcheck probe g\n"
    "    sh = jnp.sum(hess * bag)  # numcheck probe h\n"
    "    return sg, sh\n")


def test_seeded_hazard_fails_gate(tmp_path):
    """Acceptance (ISSUE 19): a raw ``jnp.sum`` over gradient state
    seeded into a copy of ``learner/serial.py`` fails the package gate
    with NUM001 at the correct file:line — library API and CLI."""
    pkg = tmp_path / "lightgbm_tpu"
    shutil.copytree(os.path.join(REPO, "lightgbm_tpu"), pkg,
                    ignore=shutil.ignore_patterns("__pycache__"))
    target = pkg / "learner" / "serial.py"
    target.write_text(target.read_text() + NUM001_SEED)
    lines = target.read_text().splitlines()
    line_g = [i + 1 for i, ln in enumerate(lines)
              if "# numcheck probe g" in ln][-1]
    line_h = line_g + 1

    findings, by_rel = run_numcheck(["lightgbm_tpu"], root=str(tmp_path))
    baseline = load_baseline(os.path.join(REPO, BASELINE_DEFAULT))
    fresh = new_findings(findings, by_rel, baseline)
    hits = {f.line for f in fresh if f.rule == "NUM001"
            and f.file == "lightgbm_tpu/learner/serial.py"}
    assert hits >= {line_g, line_h}, [f.render() for f in fresh]

    # ... and the CLI exits non-zero printing file:line + rule id
    proc = subprocess.run(
        [sys.executable, "-m", "tools.numcheck", "--root", str(tmp_path),
         "lightgbm_tpu"],
        cwd=REPO, capture_output=True, text=True)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert (f"lightgbm_tpu/learner/serial.py:{line_g}: NUM001"
            in proc.stdout), proc.stdout
    assert (f"lightgbm_tpu/learner/serial.py:{line_h}: NUM001"
            in proc.stdout), proc.stdout


# ---------------------------------------------------------------------------
# 4a. registry coherence: names shared with the runtime halves
# ---------------------------------------------------------------------------
def test_tolerance_rows_well_formed():
    for name, row in TOLERANCES.items():
        assert isinstance(row["value"], (int, float)), name
        for key in ("why", "contract", "unit"):
            assert str(row.get(key, "")).strip(), (name, key)
        assert tol(name) == float(row["value"])
    with pytest.raises(KeyError):
        tol("no_such_budget")


def test_ulp_budget_shared_by_name_with_runtime_contract():
    from lightgbm_tpu.obs import num_contract
    assert num_contract.ULP_BUDGET == tol(num_contract.BUDGET_NAME)
    assert num_contract.BUDGET_NAME in TOLERANCES


def test_stream_chunk_mirrors_device_grid():
    from lightgbm_tpu.learner import serial
    from lightgbm_tpu.obs import num_contract
    assert num_contract.STREAM_CHUNK == serial.STREAM_CHUNK


def test_envelope_margins_shared_by_name():
    """parallel/envelope.py's measured flip-envelope margins are the
    registry rows — a recalibration must update BOTH or this fails."""
    import inspect
    from lightgbm_tpu.parallel.envelope import assert_envelope
    sig = inspect.signature(assert_envelope)
    assert sig.parameters["rel_margin"].default == tol("envelope_rel")
    assert sig.parameters["abs_margin"].default == tol("envelope_abs")


def test_registered_contexts_exist():
    """Every sanctioned reducer/context/fence/compensation entry names
    a real function in a real module (NUM000 checks this statically;
    this pins it from the test side too)."""
    import ast
    for table in (reg.REDUCERS, reg.CONTEXTS, reg.FENCE_CONTEXTS,
                  reg.COMPENSATED):
        for d in table:
            func = d.get("function") or d.get("name")
            path = os.path.join(REPO, d["module"])
            assert os.path.exists(path), d
            tree = ast.parse(open(path).read())
            defined = {n.name for n in ast.walk(tree)
                       if isinstance(n, (ast.FunctionDef,
                                         ast.AsyncFunctionDef))}
            assert func in defined, d
            assert d["why"].strip(), d


# ---------------------------------------------------------------------------
# 4b. migration helpers are bitwise no-ops
# ---------------------------------------------------------------------------
def _bits_equal(a, b):
    a = np.asarray(a)
    b = np.asarray(b)
    assert a.dtype == b.dtype and a.shape == b.shape
    return a.tobytes() == b.tobytes()


def test_select_miss_bin_bitwise():
    import jax.numpy as jnp
    from lightgbm_tpu.ops.split import _select_miss_bin
    rng = np.random.default_rng(0)
    L, F, B = 4, 5, 8
    g = jnp.asarray(rng.normal(size=(L, F, B)).astype(np.float32))
    h = jnp.asarray(np.abs(rng.normal(size=(L, F, B))).astype(np.float32))
    c = jnp.asarray(rng.integers(0, 9, size=(L, F, B)).astype(np.float32))
    miss = np.zeros((F, B), bool)       # one-hot over the bin axis
    miss[:, 3] = True
    m = jnp.asarray(miss)
    got = _select_miss_bin(m, g, h, c)
    want = (jnp.sum(jnp.where(m[None], g, 0.0), axis=-1),
            jnp.sum(jnp.where(m[None], h, 0.0), axis=-1),
            jnp.sum(jnp.where(m[None], c, 0.0), axis=-1))
    for a, b in zip(got, want):
        assert _bits_equal(a, b)


def test_fold_pair_grid_bitwise():
    import jax.numpy as jnp
    from lightgbm_tpu.objective.objectives import _fold_pair_grid
    rng = np.random.default_rng(1)
    T, M = 6, 8
    signed = jnp.asarray(rng.normal(size=(T, M)).astype(np.float32))
    hh = jnp.asarray(np.abs(rng.normal(size=(T, M))).astype(np.float32))
    g_got, h_got = _fold_pair_grid(signed, hh, T, M)
    g_want = (jnp.pad(jnp.sum(signed, axis=1), (0, M - T))
              - jnp.sum(signed, axis=0))
    h_want = (jnp.pad(jnp.sum(hh, axis=1), (0, M - T))
              + jnp.sum(hh, axis=0))
    assert _bits_equal(g_got, g_want) and _bits_equal(h_got, h_want)


def test_sum_tree_axis_bitwise():
    import jax.numpy as jnp
    from lightgbm_tpu.models.tree import _sum_tree_axis
    rng = np.random.default_rng(2)
    per_tree = jnp.asarray(rng.normal(size=(5, 64)).astype(np.float32))
    assert _bits_equal(_sum_tree_axis(per_tree),
                       jnp.sum(per_tree, axis=0))


def test_select_row_leaf_bitwise():
    import jax.numpy as jnp
    from lightgbm_tpu.learner.serial import _select_row_leaf
    rng = np.random.default_rng(3)
    L, N = 7, 50
    leaf_value = jnp.asarray(rng.normal(size=L).astype(np.float32))
    sel_np = np.zeros((L, N), bool)
    sel_np[rng.integers(0, L, size=N), np.arange(N)] = True
    sel = jnp.asarray(sel_np)
    assert _bits_equal(
        _select_row_leaf(sel, leaf_value),
        jnp.sum(jnp.where(sel, leaf_value[:, None], 0.0), axis=0))


def test_abs_grad_importance_bitwise():
    import jax.numpy as jnp
    from lightgbm_tpu.boosting.variants import _abs_grad_importance
    rng = np.random.default_rng(4)
    G = jnp.asarray(rng.normal(size=(40, 3)).astype(np.float32))
    H = jnp.asarray(np.abs(rng.normal(size=(40, 3))).astype(np.float32))
    assert _bits_equal(_abs_grad_importance(G, H),
                       jnp.sum(jnp.abs(G * H), axis=1))


# ---------------------------------------------------------------------------
# 4c. the runtime ulp contract (obs/num_contract.py)
# ---------------------------------------------------------------------------
def test_canonical_root_sum_matches_device_reducer():
    """The NumPy mirror performs bit-for-bit the same adds as the
    device-side canonical reduction — the property that lets the host
    replay the device tree exactly."""
    import jax.numpy as jnp
    from lightgbm_tpu.learner.serial import (reduce_chunk_sums,
                                             root_chunk_sums)
    from lightgbm_tpu.obs.num_contract import canonical_root_sum
    rng = np.random.default_rng(5)
    for n in (1, 100, 8192, 20_000):
        x = rng.normal(size=n).astype(np.float32)
        bag = jnp.ones(n, bool)
        sg, _, _ = reduce_chunk_sums(
            root_chunk_sums(jnp.asarray(x), jnp.asarray(x), bag))
        assert _bits_equal(np.float32(sg), canonical_root_sum(x)), n


def test_ulp_diff_basics():
    from lightgbm_tpu.obs.num_contract import ulp_diff
    one = np.float32(1.0)
    nxt = np.nextafter(one, np.float32(2.0))
    assert ulp_diff(one, one) == 0
    assert ulp_diff(one, nxt) == 1
    assert ulp_diff(nxt, one) == 1
    assert ulp_diff(np.float32(0.0), np.float32(-0.0)) == 0
    assert ulp_diff(np.float32(-1.0), np.float32(1.0)) > 1_000_000


def test_window_check_ledger_and_trip(monkeypatch):
    from lightgbm_tpu.obs import num_contract
    monkeypatch.setenv("LGBM_TPU_NUM_CONTRACT", "1")
    num_contract.reset()
    s = np.linspace(-1.0, 1.0, 1000).astype(np.float32)
    drift = num_contract.window_check(s, it=2)
    assert drift is not None and drift <= num_contract.ULP_BUDGET
    assert len(num_contract.ledger()) == 1
    assert num_contract.ledger()[0][0] == 2
    assert not num_contract.trips()
    # non-finite scores are the health sentinel's jurisdiction
    bad = s.copy()
    bad[0] = np.nan
    assert num_contract.window_check(bad, it=3) is None
    assert len(num_contract.ledger()) == 1
    # a trip is sticky degradation, not an exception
    from lightgbm_tpu.obs import health
    monkeypatch.setattr(num_contract, "ULP_BUDGET", -1)
    try:
        drift = num_contract.window_check(s, it=4)
        assert num_contract.trips() and \
            num_contract.trips()[0]["window_it"] == 4
        assert num_contract.section()["trips"]
    finally:
        health.reset()
        num_contract.reset()


def test_window_check_disabled_is_noop(monkeypatch):
    from lightgbm_tpu.obs import num_contract
    monkeypatch.delenv("LGBM_TPU_NUM_CONTRACT", raising=False)
    num_contract.reset()
    assert num_contract.window_check(np.ones(8, np.float32), it=1) is None
    assert not num_contract.ledger()


def test_ledger_oracle_hex_is_exact():
    """The ledger records the f64 oracle as float.hex() so two runs
    compare EXACTLY — the field the identity harness diffs."""
    from lightgbm_tpu.obs import num_contract
    os.environ["LGBM_TPU_NUM_CONTRACT"] = "1"
    try:
        num_contract.reset()
        s = np.arange(100, dtype=np.float32) / 7.0
        num_contract.window_check(s, it=1)
        (_, _, hx), = num_contract.ledger()
        assert float.fromhex(hx) == float(np.asarray(s, np.float64).sum())
    finally:
        os.environ.pop("LGBM_TPU_NUM_CONTRACT", None)
        num_contract.reset()


def test_identity_check_full_matrix():
    """The one-command harness passes the FULL partition matrix on CPU
    (acceptance: ISSUE 19; streamed-kernel groups ISSUE 20) —
    serial/stream1 byte-identical at S=1, mesh2/mesh2_block0/stream2/
    elastic1 byte-identical at S=2, the forced-backend pairs
    byte-identical within S=1·pallas, zero ulp-budget
    trips, with the determinism ledger and the num contract armed.
    Subprocess: the harness pins a 2-device host pool via XLA_FLAGS
    before jax initializes, which this process cannot."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": REPO + os.pathsep + os.environ.get(
               "PYTHONPATH", "")}
    env.pop("XLA_FLAGS", None)
    env.pop("LGBM_TPU_FAULTS", None)
    proc = subprocess.run(
        [sys.executable, "-m", "tools.identity_check", "--json"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "S=1: OK" in proc.stdout, proc.stdout
    assert "S=2: OK" in proc.stdout, proc.stdout
    payload = [ln for ln in proc.stdout.splitlines()
               if ln.startswith("{")]
    assert payload, proc.stdout
    import json
    rec = json.loads(payload[-1])
    assert "S=1·pallas: OK" in proc.stdout, proc.stdout
    assert rec["identity_check_ok"] is True
    assert set(rec["scenarios"]) == {"serial", "stream1", "mesh2",
                                     "mesh2_block0", "stream2",
                                     "elastic1", "serial_pallas",
                                     "stream1_pallas"}
