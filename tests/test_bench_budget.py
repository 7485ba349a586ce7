"""Budget-proofing of bench.py (VERDICT r5 Weak #1 / PR 4 satellite).

Round 5's driver timeout mid-ranking-leg produced an artifact with
rc=124 and ``parsed: null`` — every leg that had already PASSED
was erased because the single JSON line only printed at the end.  The
contract under test:

* a parseable, self-contained headline line is flushed right after the
  first synthetic leg (so a kill at ANY later point still leaves a
  non-null artifact for a driver that takes the last parseable line);
* past ``BENCH_DEADLINE_S``, every remaining auxiliary leg records an
  explicit ``"skipped: budget"`` marker instead of running;
* the final line is complete, parseable, and still carries the
  headline numbers.

The subprocess runs at toy shape (2k rows, 2 iters, 7 leaves) on CPU —
this exercises emission/skip mechanics, not throughput.
"""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _parse_lines(stdout):
    out = []
    for ln in stdout.splitlines():
        ln = ln.strip()
        if not ln.startswith("{"):
            continue
        try:
            out.append(json.loads(ln))
        except ValueError:
            pass
    return out


@pytest.fixture(scope="module")
def bench_run(tmp_path_factory):
    env = {**os.environ,
           "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": REPO + os.pathsep + os.environ.get(
               "PYTHONPATH", ""),
           # toy shapes: mechanics, not throughput
           "BENCH_ROWS": "2000", "BENCH_ITERS": "2",
           "BENCH_LEAVES": "7", "BENCH_BIN": "15",
           "BENCH_FULL": "0",
           # the deadline is already exceeded when the aux legs are
           # reached: they must all record "skipped: budget"
           "BENCH_DEADLINE_S": "0.000001"}
    env.pop("XLA_FLAGS", None)
    env.pop("BENCH_DATA", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=420)
    return proc


def test_headline_line_survives_simulated_timeout(bench_run):
    """The FIRST emitted line is a self-contained non-null headline:
    killing the process at any point after it (the r05 timeout
    scenario) leaves a parseable artifact."""
    assert bench_run.returncode == 0, bench_run.stdout + bench_run.stderr
    lines = _parse_lines(bench_run.stdout)
    assert len(lines) >= 2, bench_run.stdout
    first = lines[0]
    assert first["metric"] == "higgs_shape_train_row_iters_per_sec"
    assert first["value"] is not None and first["value"] > 0
    assert "vs_baseline" in first
    assert first.get("partial") == "headline-1M"
    # ISSUE 12: the headline leg stamps its canonical model digest
    assert isinstance(first.get("model_digest"), str) \
        and len(first["model_digest"]) == 64


def test_headline_carries_peak_hbm_field(bench_run):
    """ISSUE 8: every emitted leg carries ``peak_hbm_bytes`` — a
    positive int where the backend exposes allocator stats, or null
    with an explicit ``peak_hbm_reason`` (the CPU tier-1 case)."""
    for line in _parse_lines(bench_run.stdout):
        assert "peak_hbm_bytes" in line, line.get("partial", "final")
        peak = line["peak_hbm_bytes"]
        if peak is None:
            assert line.get("peak_hbm_reason"), line
        else:
            assert isinstance(peak, int) and peak > 0


def test_deadline_skips_aux_legs_with_markers(bench_run):
    final = _parse_lines(bench_run.stdout)[-1]
    assert "partial" not in final           # the complete line
    assert final["value"] > 0               # headline retained
    for leg in ("serve", "serve_load", "valid", "bin255", "rank", "rank63",
                "multichip", "split_finder", "rank_grad", "attribution",
                "stream", "elastic"):
        assert final.get(f"{leg}_leg") == "skipped: budget", final
    assert final.get("real_data") == "skipped: budget"
    assert set(final.get("legs_skipped", [])) >= {
        "serve", "serve_load", "valid", "bin255", "rank", "rank63",
        "multichip", "split_finder", "rank_grad", "attribution", "stream",
        "elastic", "num_contract"}
    # an explicit skip is not a failure: no legs_failed / hard-failed
    assert "legs_failed" not in final
    assert "legs_hard_failed" not in final
    assert final["deadline_s"] > 0 and final["elapsed_s"] >= 0


def test_dryrun_emits_wave_table_and_north_star_parses():
    """`bench.py --dryrun` must emit the per-active-slot-bucket wave
    table (the deep-wave ns/row regression tracker) and confirm the
    committed north_star.json wave_kernel entries parse — the
    mechanics gate for the BENCH_r* wave recording."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": REPO + os.pathsep + os.environ.get(
               "PYTHONPATH", "")}
    env.pop("XLA_FLAGS", None)
    # 600 s: the num_contract leg (ISSUE 19) adds an in-process
    # contract-armed toy train plus a drift-proof child that trains the
    # identity matrix on top of the elastic chaos subprocess
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--dryrun"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    lines = _parse_lines(proc.stdout)
    assert lines, proc.stdout
    out = lines[-1]
    assert out["metric"] == "wave_kernel_ns_per_row" and out["dryrun"]
    buckets = {r["active"] for r in out["wave_kernel"]}
    assert buckets >= {8, 32, 64, 128}
    for r in out["wave_kernel"]:
        assert r["wide_ns_per_row"] > 0
    assert out["north_star_parse_ok"] is True
    assert set(out["north_star_wave_buckets"]) >= {32, 64, 128}
    # serve (predict) leg schema gate: the dryrun runs the REAL leg at
    # toy shape and validates every field the TPU artifact will carry —
    # rows/s, the host-traversal anchor, per-bucket p50/p99, and the
    # parity + zero-recompile verdicts (PR 6 satellite)
    assert out["serve_schema_ok"] is True, out
    from bench import SERVE_SCHEMA_KEYS
    for key in SERVE_SCHEMA_KEYS:
        assert key in out, key
    assert out["serve_rows_per_sec"] > 0
    assert out["serve_host_rows_per_sec"] > 0
    assert out["serve_parity_ok"] is True
    assert out["serve_recompile_ok"] is True
    assert out["serve_steady_recompiles"] == 0
    assert out["serve_requests"] > 0
    for rec in out["serve_latency_ms"].values():
        # ISSUE 13: the rolling sketch adds the p99.9 tail column
        assert rec["count"] > 0
        assert rec["p999"] >= rec["p99"] >= rec["p50"] >= 0.0
    # serve_load QPS-sweep gate (ISSUE 13): the REAL open-loop Poisson
    # sweep ran at toy duration — offered vs achieved QPS and the
    # p50/p99/p99.9 tail columns on every step, zero failed requests,
    # and the north_star.json serve_load spec parses
    assert out["serve_load_ok"] is True, out.get(
        "serve_load_leg", out.get("serve_load_schema_missing"))
    from bench import SERVE_LOAD_SCHEMA_KEYS
    for key in SERVE_LOAD_SCHEMA_KEYS:
        assert key in out, key
    assert len(out["serve_load_table"]) == len(out["serve_load_qps_sweep"])
    for row in out["serve_load_table"]:
        assert row["offered_qps"] > 0 and row["achieved_qps"] > 0
        assert row["failures"] == 0
        assert row["p999_ms"] >= row["p99_ms"] >= row["p50_ms"] >= 0.0
    assert out["north_star_aux_detail"]["serve_load"] in (
        "measured", "pending-capture"), out["north_star_aux_detail"]
    # multichip mechanics gate (PR 7 + ISSUE 11): the REAL leg ran on
    # a 2-device virtual CPU pool (re-exec'd child) — schema complete,
    # fused/unfused (LGBM_TPU_MESH_BLOCK) measured, the two models
    # byte-identical (the bit-parity contract), and
    # the dispatch-gap columns populated on both dispatch modes
    from bench import MULTICHIP_SCHEMA_KEYS
    assert out["multichip_schema_ok"] is True, out.get(
        "multichip_leg", out.get("multichip_schema_missing"))
    for key in MULTICHIP_SCHEMA_KEYS:
        assert key in out, key
    assert out["multichip_devices_visible"] >= 2
    assert out["multichip_parity_ok"] is True
    assert out["multichip_serial_row_iters_per_sec"] > 0
    for row in out["multichip_table"]:
        assert row["devices"] >= 2
        assert row["row_iters_per_sec"] > 0
        assert row["unfused_row_iters_per_sec"] > 0
        assert row["scaling_efficiency"] > 0
        assert row["fused_speedup"] > 0
        assert row["unfused_dispatch_gap_mean_s"] is not None
    # extended north_star tables (255-bin / MSLR / multichip): either
    # measured rows or an explicit pending-capture spec — and the toy
    # aux wave tables actually ran
    assert out["north_star_aux_ok"] is True, out.get(
        "north_star_aux_detail")
    assert out["wave_aux_ok"] is True, out.get("wave_aux_error")
    for key in ("wave_kernel_255", "wave_kernel_mslr"):
        assert all(r["wide_ns_per_row"] > 0 for r in out[key]), out[key]
    # split-finder microbench gate (ISSUE 9): the cached changed-slot
    # scan beats the LGBM_TPU_SPLIT_CACHE=0 full rescan >= 4x at the
    # 255-leaf/255-bin shape, and every shape row is present and sane
    assert out["split_finder_ok"] is True, out.get(
        "split_finder_leg", out.get("split_finder"))
    shapes = {(r["leaves"], r["max_bin"]) for r in out["split_finder"]}
    assert shapes == {(63, 63), (63, 255), (255, 63), (255, 255)}
    for r in out["split_finder"]:
        assert r["cached_us_per_wave"] > 0 and r["full_us_per_wave"] > 0
        assert r["cached_slots"] < r["full_slots"]
    assert out["split_finder_speedup_255"] >= 4.0
    # rank_grad microbench gate (ISSUE 9 satellite): measured ns/doc at
    # the MSLR bucket mix AND one obj.rank_grad.<M> span per bucket
    assert out["rank_grad_ok"] is True, out.get("rank_grad_leg")
    from bench import RANK_GRAD_SCHEMA_KEYS
    for key in RANK_GRAD_SCHEMA_KEYS:
        assert key in out, key
    assert out["rank_grad_ns_per_doc"] > 0
    assert out["rank_grad_buckets"] > 0
    assert len(out["rank_grad_bucket_spans"]) == out["rank_grad_buckets"]
    # the extended north_star specs validate alongside the wave tables
    for key in ("split_finder", "rank_grad"):
        assert out["north_star_aux_detail"][key] in (
            "measured", "pending-capture"), out["north_star_aux_detail"]
    # stream_ingest gate (ISSUE 14): the REAL out-of-core leg ran at
    # toy shape — multi-shard ingest into the mmap store, MULTI-block
    # streamed training BYTE-identical to resident in-memory training,
    # a real SIGKILL mid-ingest resuming to the clean manifest, and
    # the throughput/memory schema the TPU artifact will record
    assert out["stream_schema_ok"] is True, out.get(
        "stream_leg", out.get("stream_schema_missing"))
    from bench import STREAM_SCHEMA_KEYS
    for key in STREAM_SCHEMA_KEYS:
        assert key in out, key
    assert out["stream_identity_ok"] is True
    assert out["stream_resume_ok"] is True
    assert out["stream_shards"] > 1          # multi-shard store
    assert out["stream_rows"] > out["stream_block_rows"]  # multi-block
    assert out["stream_ingest_rows_per_sec"] > 0
    assert out["stream_row_iters_per_sec"] > 0
    assert out["stream_host_rss_peak_bytes"] > 0
    assert isinstance(out["stream_model_digest"], str) \
        and len(out["stream_model_digest"]) == 64
    # ISSUE 20: the A/B columns — resolved backend, ledger rows/s, and
    # the two speedup verdicts (sanity on CPU, throughput on TPU)
    assert out["stream_backend"] in ("scatter", "pallas")
    assert out["stream_rows_per_sec"] > 0
    assert out["stream_kernel_speedup"] > 0
    assert out["stream_pipeline_speedup"] > 0
    assert out["north_star_aux_detail"]["stream_ingest"] in (
        "measured", "pending-capture"), out["north_star_aux_detail"]
    # elastic chaos gate (ISSUE 16): the REAL SIGKILL shrink+regrow
    # scenario ran in a CPU subprocess — one worker killed mid-window,
    # the survivor re-rendezvoused and resumed from the last committed
    # barrier, a replacement joiner regrew the world, and BOTH results
    # are byte-identical to the uninterrupted 1-process oracle
    assert out["elastic_ok"] is True, out.get(
        "elastic_leg", out.get("elastic_errors"))
    from bench import ELASTIC_SCHEMA_KEYS
    for key in ELASTIC_SCHEMA_KEYS:
        assert key in out, key
    assert out["elastic_identity_ok"] is True
    assert out["elastic_recovery_ok"] is True
    assert out["elastic_workers"] >= 2
    assert out["elastic_respawned"]
    assert out["elastic_wall_s"] > 0
    assert isinstance(out["elastic_oracle_sha256"], str) \
        and len(out["elastic_oracle_sha256"]) == 64
    # MTTR accounting (ISSUE 17): the killed run reported a positive
    # recovery time whose phase breakdown sums to it exactly
    assert out["elastic_mttr_s"] > 0
    phases = out["elastic_mttr_phases"]
    assert set(phases) == {"detect", "resync", "reshard", "restore",
                           "retrain"}
    assert abs(sum(phases.values()) - out["elastic_mttr_s"]) < 1e-9
    assert out["north_star_aux_detail"]["elastic"] in (
        "measured", "pending-capture"), out["north_star_aux_detail"]
    # numerics ulp-contract gate (ISSUE 19): the contract-armed toy
    # train held the score_root_ulp budget on every output window, and
    # the env-armed num.reassoc child (raw jnp.sum in place of the
    # canonical chunk+pairwise root reducer) broke the digest law
    # LOUDLY — identity_check exits nonzero and names the first
    # diverging partition pair
    assert out["num_contract_schema_ok"] is True, out.get(
        "num_contract_leg", out.get("num_contract_schema_missing"))
    from bench import NUM_CONTRACT_SCHEMA_KEYS
    for key in NUM_CONTRACT_SCHEMA_KEYS:
        assert key in out, key
    assert out["num_contract_ok"] is True
    assert out["num_contract_windows"] > 0
    assert out["num_contract_trips"] == 0
    assert out["num_contract_max_drift_ulps"] <= \
        out["num_contract_budget_ulps"]
    assert out["num_contract_budget_name"] == "score_root_ulp"
    assert out["num_reassoc_drift_proof_ok"] is True
    assert "first diverging pair" in out["num_reassoc_divergence"]
    # device-time attribution gate (ISSUE 10): the REAL leg ran at toy
    # shape — windowed LGBM_TPU_PROFILE capture, parsed, >= 90% of the
    # captured device time attributed to named spans, host-gap and
    # per-program cost-model FLOPs/bytes populated
    assert out["attribution_schema_ok"] is True, out.get(
        "attribution_leg", out.get("attribution_schema_missing"))
    from bench import ATTRIBUTION_SCHEMA_KEYS
    for key in ATTRIBUTION_SCHEMA_KEYS:
        assert key in out, key
    assert out["attribution_device_time_s"] > 0
    assert out["attribution_coverage"] >= 0.90
    assert out["attribution_spans"]
    assert out["attribution_host_gap_frac"] is not None
    assert out["attribution_dispatch_gap_mean_s"] is not None
    assert any(r["flops"] for r in out["attribution_cost_programs"])
    assert out["north_star_aux_detail"]["device_attribution"] in (
        "measured", "pending-capture")
    # perf-ledger gate (ISSUE 10): the cross-round trend table loads
    # every committed BENCH_r*.json (unparsed rounds visible; none is
    # committed since PR 21 and the gate holds on that) and the newest
    # parsed round does not regress >10% vs the best prior
    assert out["perf_ledger_ok"] is True, out.get(
        "perf_ledger_error", out.get("perf_ledger_regressions"))
    from tools.perf_ledger import load_history
    committed = load_history(REPO)          # empty since PR 21
    assert out["perf_ledger_rounds"] == [h["round"] for h in committed]
    assert out["perf_ledger_parsed_rounds"] == [
        h["round"] for h in committed if h["parsed"]]
    # model-digest reproducibility gate (ISSUE 12): every model-
    # training leg stamps the canonical sha256 (obs/determinism.py) and
    # two toy trainings from identical seeds agree — the bench's own
    # train-twice contract, so a TPU BENCH_r* capture settles
    # cross-host reproducibility for free
    assert out["model_digest_repeat_ok"] is True, out.get(
        "model_digest_error")
    assert isinstance(out["model_digest"], str) \
        and len(out["model_digest"]) == 64
    for row in out["multichip_table"]:
        assert isinstance(row["model_digest"], str) \
            and len(row["model_digest"]) == 64
    # per-leg memory column (ISSUE 8): every dryrun leg carries
    # peak_hbm_bytes — int > 0 with allocator stats, else null + reason
    assert out["peak_hbm_schema_ok"] is True, out
    for key in ("peak_hbm_bytes", "waves_peak_hbm_bytes",
                "multichip_peak_hbm_bytes", "serve_peak_hbm_bytes",
                "stream_peak_hbm_bytes"):
        assert key in out, key
        if out[key] is None:
            assert out.get("peak_hbm_reason"), out
        else:
            assert out[key] > 0


def test_north_star_wave_entries_parse():
    """The committed artifact itself: every wave_kernel entry carries a
    positive active-slot bucket and ns/row (what the bench table and
    ISSUE arithmetic consume)."""
    path = os.path.join(REPO, "tests", "data", "north_star.json")
    with open(path) as fh:
        ns = json.load(fh)
    wk = ns["wave_kernel"]
    assert len(wk) >= 3
    for row in wk:
        assert int(row["active"]) > 0
        assert float(row["ns_per_row"]) > 0
        assert float(row["mxu_util_vs_measured_peak"]) > 0


def test_gate_bearing_hard_failure_zeroes_headline():
    """ADVICE r5 #2: a gate-bearing leg (here: valid) that crashes BOTH
    attempts with the same deterministic error must zero vs_baseline —
    legs_hard_failed alone must not leave the headline green."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": REPO + os.pathsep + os.environ.get(
               "PYTHONPATH", ""),
           "BENCH_ROWS": "2000", "BENCH_ITERS": "2",
           "BENCH_LEAVES": "7", "BENCH_BIN": "15",
           "BENCH_FULL": "0", "BENCH_255": "0", "BENCH_RANK": "0",
           "BENCH_WAVES": "0", "BENCH_SERVE": "0",
           "BENCH_SERVE_LOAD": "0",
           "BENCH_ATTRIBUTION": "0",   # this test gates the valid leg
           "BENCH_ELASTIC": "0",       # chaos scenario covered elsewhere
           "BENCH_FORCE_FAIL": "valid"}
    env.pop("XLA_FLAGS", None)
    env.pop("BENCH_DATA", None)
    env.pop("BENCH_DEADLINE_S", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=420)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    final = _parse_lines(proc.stdout)[-1]
    assert final.get("legs_hard_failed") == ["valid"], final
    assert "forced failure" in final.get("valid_leg", ""), final
    assert final["vs_baseline"] == 0.0, final
    assert final["value"] > 0          # the headline NUMBER is retained


def test_split_finder_rank_grad_attribution_survive_midrun_kill():
    """ISSUE 9/10 satellite: the split_finder, rank_grad, and
    device-time attribution tables are emitted INCREMENTALLY (each as
    its own partial line, right after the headline) — a hard kill
    (SIGKILL, the driver-timeout class) immediately after the
    attribution checkpoint must leave a last parseable line that
    carries ALL of them."""
    import time
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": REPO + os.pathsep + os.environ.get(
               "PYTHONPATH", ""),
           "BENCH_ROWS": "2000", "BENCH_ITERS": "2",
           "BENCH_LEAVES": "7", "BENCH_BIN": "15", "BENCH_FULL": "0",
           # toy attribution-leg shape: the profiled capture + parse
           # must stay seconds, not the real-leg 100k-row minutes
           "BENCH_ATTR_ROWS": "1500", "BENCH_ATTR_ITERS": "6",
           "BENCH_ATTR_FEATURES": "5", "BENCH_ATTR_LEAVES": "7",
           "BENCH_ATTR_BIN": "15"}
    env.pop("XLA_FLAGS", None)
    env.pop("BENCH_DATA", None)
    env.pop("BENCH_DEADLINE_S", None)
    proc = subprocess.Popen(
        [sys.executable, os.path.join(REPO, "bench.py")],
        cwd=REPO, env=env, stdout=subprocess.PIPE,
        stderr=subprocess.DEVNULL, text=True)
    lines, deadline = [], time.time() + 390
    try:
        for ln in proc.stdout:
            lines.append(ln)
            if '"headline-1M+attribution"' in ln \
                    or time.time() > deadline:
                break
    finally:
        proc.kill()
        proc.wait(30)
    parsed = _parse_lines("".join(lines))
    assert parsed, "".join(lines)
    last = parsed[-1]
    assert last.get("partial") == "headline-1M+attribution", last
    # the kill happened mid-run; the artifact already carries all three
    assert last["value"] > 0
    table = last["split_finder"]
    assert {(r["leaves"], r["max_bin"]) for r in table} == {
        (63, 63), (63, 255), (255, 63), (255, 255)}
    assert all(r["cached_us_per_wave"] > 0
               and r["full_us_per_wave"] > 0 for r in table)
    assert last["rank_grad_ns_per_doc"] > 0
    assert len(last["rank_grad_bucket_spans"]) > 0
    # attribution (ISSUE 10): captured, parsed, on the artifact before
    # the kill — deadline/SIGKILL-survivable like the PR 9 tables
    assert last["attribution_device_time_s"] > 0
    assert last["attribution_coverage"] >= 0.90
    assert last["attribution_spans"], last


def test_auc_gate_tightened_beyond_085(bench_run):
    """VERDICT r5 Weak #7: the synthetic AUC floor must sit at the
    recorded-r4-calibrated default (0.93), not the old 0.85 — and be
    recorded in the artifact so a reader can see what gated it."""
    final = _parse_lines(bench_run.stdout)[-1]
    assert final["auc_gate"] >= 0.93
    # toy-shape AUC may legitimately miss the gate; what matters is the
    # verdict is derived from THIS gate and the headline value survives
    assert final["auc_ok"] == (final["train_auc"] >= final["auc_gate"])
