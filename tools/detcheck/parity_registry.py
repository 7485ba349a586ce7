"""The declarative parity/contract registry detcheck checks against.

PR 11's hard-won lesson was that two XLA programs computing the "same"
logic are only byte-identical when a TEST pins them together — chasing
cross-program FMA-contraction parity analytically is unwinnable.  This
file turns that lesson into a checked contract: every DUAL-PATH SEAM
(an env flag selecting between traced programs) and every ORDER-
SENSITIVE SELECTION (argmax/top_k in split-selection or serve code)
must either name the test that pins its parity / tie-break behavior,
or carry an explicit exemption with the argument why no gate is
needed.  A new seam that registers nothing is a DET004/DET005 finding.

Three tables:

* :data:`PROGRAM_PAIRS` — env-flag seams that select between traced
  programs, each mapped to the pinning test (DET005).  ``programs`` is
  documentation: the two (or more) compiled paths the flag chooses
  between.
* :data:`EXEMPT_ENV` — env knobs that look like seams to the analyzer
  (they gate branches in jit-bearing modules) but do NOT select
  between parity-relevant programs; each carries its why (DET005).
* :data:`TIE_BREAK` — modules whose ``argmax``/``argmin``/``top_k``
  calls decide model structure or served output, mapped to the test
  pinning the first-max tie-break (DET004).  A module can instead
  declare ``TIE_BREAK_CONTRACT = "<test path>"`` at module scope —
  the in-file form of the same registration.

Registered test paths are resolved against the REPO root (where this
tools/ package lives), not the analyzed root, so seeded-hazard tests
that copy ``lightgbm_tpu/`` into a temp dir still validate against the
real test suite.  A registered test whose file does not exist is itself
a finding (the gate rotted).
"""
from __future__ import annotations

import os
from typing import Dict, Optional, Tuple

# repo root = parent of tools/ (this file lives in tools/detcheck/)
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# -- DET005: env-flag program seams --------------------------------------
PROGRAM_PAIRS: Tuple[Dict, ...] = (
    {"name": "mesh-fused-vs-per-iteration",
     "env": "LGBM_TPU_MESH_BLOCK",
     "programs": ("fused lax.scan mesh block (one dispatch per window)",
                  "length-1 blocks of the same compiled body"),
     "test": "tests/test_mesh_block.py"},
    {"name": "block-vs-legacy-eager",
     "env": "LGBM_TPU_NO_BLOCK",
     "programs": ("fused scan-block training loop",
                  "legacy eager per-iteration loop"),
     "test": "tests/test_block_valid.py"},
    {"name": "split-cache-vs-full-rescan",
     "env": "LGBM_TPU_SPLIT_CACHE",
     "programs": ("incremental per-leaf split cache (O(new children))",
                  "full O(L*F*B) per-wave rescan"),
     "test": "tests/test_split_cache.py"},
    {"name": "pallas-split-kernel-vs-xla-scan",
     "env": "LGBM_TPU_SPLIT_KERNEL",
     "programs": ("fused Pallas split kernel",
                  "chunked XLA scan split finder"),
     "test": "tests/test_pallas_split.py"},
    {"name": "split-kernel-interpret-vs-compiled",
     "env": "LGBM_TPU_SPLIT_INTERPRET",
     "programs": ("Pallas split kernel, interpret mode",
                  "Pallas split kernel, compiled"),
     "test": "tests/test_pallas_split.py"},
    {"name": "hist-backend-selection",
     "env": "LGBM_TPU_HIST_BACKEND",
     "programs": ("scatter histogram", "wide fused Pallas kernel",
                  "their accumulator-seeded streamed-fold twins "
                  "(learner/serial.py make_hist_fold_fn; streamed=="
                  "resident per backend pinned by "
                  "tests/test_streaming.py)"),
     "test": "tests/test_learner.py"},
    {"name": "hist-mode-precision",
     "env": "LGBM_TPU_HIST_MODE",
     "programs": ("f32 histogram accumulation",
                  "bf16/int8h accumulation modes"),
     "test": "tests/test_hist_parity.py"},
    {"name": "donation-on-vs-off",
     "env": "LGBM_TPU_DONATE",
     "programs": ("score/grad/hess buffers donated in place",
                  "undonated dispatches"),
     "test": "tests/test_mesh_block.py"},
    {"name": "phases-driver-vs-fused-build",
     "env": "LGBM_TPU_TIMETAG",
     "programs": ("unfused per-phase-timed wave driver",
                  "single jitted tree build"),
     "test": "tests/test_learner.py"},
    {"name": "lean-vs-padded-compile-shapes",
     "env": "LGBM_TPU_COMPILE_LEAN_ROWS",
     "programs": ("row-lean compile shapes", "padded compile shapes"),
     "test": "tests/test_consistency.py"},
    {"name": "device-vs-host-serve-scorer",
     "env": "LGBM_TPU_PREDICT_DEVICE",
     "programs": ("TPU-resident tensorized scorer (serve/compiler.py)",
                  "host numpy tree walk"),
     "test": "tests/test_serve.py"},
    {"name": "capi-device-vs-host-predict",
     "env": "LGBM_TPU_CAPI_DEVICE",
     "programs": ("C-API predict through the device scorer",
                  "C-API predict through the host walk"),
     "test": "tests/test_c_api.py"},
    {"name": "dart-keyed-vs-host-rng",
     "env": "LGBM_TPU_DART_HOST_RNG",
     "programs": ("pure (drop_seed, iteration)-keyed drop derivation",
                  "legacy stateful np.random.RandomState stream"),
     "test": "tests/test_determinism.py"},
    {"name": "stream-vs-resident",
     "env": "LGBM_TPU_STREAM_ROWS",
     "programs": ("streamed block trainer (boosting/streaming.py: "
                  "out-of-core mmap blocks, carried-accumulator "
                  "histogram folds — row-order scatter AND the "
                  "accumulator-seeded Pallas kernel folds — "
                  "host-resident scores)",
                  "resident in-memory fused training loop"),
     "test": "tests/test_streaming.py"},
    {"name": "stream-pipeline-vs-serial",
     "env": "LGBM_TPU_STREAM_PIPELINE",
     "programs": ("depth-2 prefetch+staging upload/compute pipeline "
                  "(block k+1 staged and device_put before block k's "
                  "fold await; fold order unchanged)",
                  "serial stage->upload->fold->await escape hatch"),
     "test": "tests/test_streaming.py"},
    {"name": "elastic-vs-single-process",
     "env": "LGBM_TPU_ELASTIC",
     "programs": ("elastic multi-host streamed training (owned-shard "
                  "folds + allgathered partials combined in shard "
                  "order, barrier-snapshot recovery)",
                  "single-process streamed training at the same "
                  "protocol shard count"),
     "test": "tests/test_elastic.py"},
    {"name": "elastic-shard-protocol",
     "env": "LGBM_TPU_ELASTIC_SHARDS",
     "programs": ("S-shard partial folds for any fixed S (the run-"
                  "lifetime identity domain; world size and membership "
                  "history never reach the traced programs)",),
     "test": "tests/test_elastic.py"},
)

# knobs that branch inside jit-bearing modules but do not choose
# between parity-relevant traced programs — each with its argument
EXEMPT_ENV: Dict[str, str] = {
    "LGBM_TPU_PROFILE": "observability: windowed profiler capture; the "
                        "captured programs are the ones already running",
    "LGBM_TPU_PROFILE_WINDOWS": "profiler capture length knob",
    "LGBM_TPU_PROFILE_ITERS": "profiler capture length knob",
    "LGBM_TPU_COST_MODEL": "observability: extra cost_analysis() compile "
                           "feeds reporting only, never training state",
    "LGBM_TPU_TRACE": "observability: JSONL event trace destination",
    "LGBM_TPU_TRACE_CONTRACT": "observability: recompile accounting "
                               "around the same programs",
    "LGBM_TPU_MEM_CONTRACT": "observability: HBM watermark sampling",
    "LGBM_TPU_MEM_TOL_BYTES": "watermark tolerance knob",
    "LGBM_TPU_MEM_TOL_FRAC": "watermark tolerance knob",
    "LGBM_TPU_MEM_LEAK_ELEMS": "fault-injection sink sizing (tests)",
    "LGBM_TPU_DETERMINISM": "observability: the determinism contract "
                            "itself (digest sampling + RNG ledger)",
    "LGBM_TPU_NUM_CONTRACT": "observability: the runtime ulp contract "
                             "(obs/num_contract.py) — per-window "
                             "canonical-vs-f64-oracle drift ledger "
                             "riding the existing score fetch; "
                             "measures numerics, never changes them",
    "LGBM_TPU_FLIGHT_RECORDER": "observability: collective fingerprint "
                                "ring; never alters the schedule",
    "LGBM_TPU_FR_CAP": "flight-recorder ring size",
    "LGBM_TPU_FAULTS": "fault-injection arming (chaos runs)",
    "LGBM_TPU_OPS_PORT": "observability: live /metrics + /healthz + "
                         "/drain HTTP plane (obs/ops_plane.py); "
                         "host-side daemon thread mirroring the run "
                         "summary, never reaches traced programs",
    "LGBM_TPU_OPS_SKETCH": "ops-plane rolling quantile-sketch window "
                           "size; reporting resolution only",
    "LGBM_TPU_WATCHDOG_S": "observability: stall-watchdog deadline "
                           "(obs/health.py); the monitor thread only "
                           "observes a wedged dispatch, it never "
                           "alters what the device computes",
    "LGBM_TPU_SENTINELS": "observability: numerics sentinels riding "
                          "window-boundary host fetches; detection "
                          "only, model state untouched",
    "LGBM_TPU_SPIKE_FACTOR": "loss-spike sentinel threshold knob",
    "LGBM_TPU_FORENSIC": "stall-forensics output path override",
    "LGBM_TPU_SYNC_FREQ": "host stop-check cadence: changes when the "
                          "host LOOKS, not what the device computes",
    "LGBM_TPU_BLOCK_CAP": "watchdog bound on iterations per dispatch; "
                          "block length is byte-identical by "
                          "construction (tests/test_mesh_block.py)",
    "LGBM_TPU_ROW_TILE": "kernel tiling knob (the upper bound of the "
                         "row tile); oracle parity on the grids "
                         "hist_tiling picks in tests/test_pallas_hist.py",
    "LGBM_TPU_SPLIT_VMEM_MB": "VMEM chunking budget; chunked==unchunked "
                              "bitwise in tests/test_split_cache.py",
    "LGBM_TPU_SPLIT_SCAN_MB": "VMEM chunking budget; chunked==unchunked "
                              "bitwise in tests/test_split_cache.py",
    "LGBM_TPU_SPLIT_CHUNK_F": "explicit chunk-width override; same "
                              "bitwise merge contract",
    "LGBM_TPU_RANK_CHUNK_PAIRS": "lambdarank pair-grid chunking; sums "
                                 "are order-preserving per bucket",
    "LGBM_TPU_PRED_TREE_CHUNK": "host predict chunking; per-tree sums "
                                "accumulate in tree order regardless",
    "LGBM_TPU_PRED_ROW_CHUNK": "host predict row chunking; rows are "
                               "independent",
    "LGBM_TPU_SERVE_ROW_CHUNK": "serve scorer row chunking; rows are "
                                "independent",
    "LGBM_TPU_NO_NATIVE": "parser backend (native C++ vs python); "
                          "parse parity pinned by tests/test_native_parser.py",
    "LGBM_TPU_RETRY_ATTEMPTS": "retry policy knob",
    "LGBM_TPU_RETRY_BASE_S": "retry policy knob",
    "LGBM_TPU_RETRY_MAX_S": "retry policy knob",
    "LGBM_TPU_RETRY_DEADLINE_S": "retry policy knob",
    "LGBM_TPU_RETRY_JITTER": "retry backoff jitter; never reaches model "
                             "state",
    "LGBM_TPU_STREAM_CACHE": "out-of-core shard-cache directory "
                             "override (io/outofcore.py); storage "
                             "location only, the cache key still "
                             "validates content",
    "LGBM_TPU_COLLECTIVE_DEADLINE_S": "rank-loss detection deadline on "
                                      "host collectives (io/distributed."
                                      "deadline_call): bounds how long "
                                      "the HOST waits, never what the "
                                      "device computes",
    "LGBM_TPU_HEARTBEAT_S": "elastic heartbeat cadence (parallel/"
                            "elastic.py); liveness signaling only, "
                            "model state untouched",
    "LGBM_TPU_ELASTIC_MEMBER": "elastic member identity (stable "
                               "member id for rejoin/chaos kill "
                               "scheduling); naming only, the rank map "
                               "is the coordinator's",
    "LGBM_TPU_FLEET_LEDGER": "observability: coordinator ops-ledger "
                             "JSONL destination (obs/fleet.py); "
                             "append-only history of the fleet, never "
                             "read back into training",
    "LGBM_TPU_CLOCK_SYNC": "observability: per-rank coordinator-clock "
                           "offset estimation; stamps trace records "
                           "only, model state untouched",
    "LGBM_TPU_COLLECTIVE_SLOW": "fault-injection straggler delay "
                                "(collective.slow); a sleep before the "
                                "collective, identity-neutral",
    "LGBM_TPU_LOCK_CONTRACT": "observability: runtime lock-order "
                              "contract (obs/lock_contract.py) — "
                              "wrapped host locks record acquisition "
                              "order and wait/hold timing, never "
                              "touching what the device computes",
    "LGBM_TPU_LOCK_HOLD_S": "observability: held-past-deadline "
                            "threshold for contract-named locks; "
                            "reporting knob only",
    "LGBM_TPU_INTERLEAVE_SEEDS": "test harness: seed count for the "
                                 "tools/interleave.py schedule fuzzer; "
                                 "never read by library code",
}

# -- DET004: first-max tie-break contracts -------------------------------
TIE_BREAK: Dict[str, Dict] = {
    "lightgbm_tpu/ops/split.py": {
        "test": "tests/test_split_cache.py",
        "pins": "chunk merge reproduces the joint argmax first-max "
                "winner BITWISE (PR 9); full-rescan parity"},
    "lightgbm_tpu/ops/pallas_split.py": {
        "test": "tests/test_pallas_split.py",
        "pins": "packed-gain kernel argmax vs XLA-scan oracle, "
                "first-lowest-bin tie order"},
    "lightgbm_tpu/parallel/learners.py": {
        "test": "tests/test_parallel.py",
        "pins": "gathered-gain argmax and voting top_k produce "
                "serial-identical trees on 2-shard meshes"},
    "lightgbm_tpu/boosting/gbdt.py": {
        "test": "tests/test_engine.py",
        "pins": "feature-mask top_k over distinct uniforms; exactly-k "
                "contract and block/non-block mask identity"},
    "lightgbm_tpu/metric/metrics.py": {
        "exempt": "multiclass-error argmax feeds a scalar metric value, "
                  "never model structure or served output"},
    "lightgbm_tpu/sklearn.py": {
        "exempt": "predicted-class argmax: numpy documents first-max; a "
                  "tie needs exactly equal f64 probabilities"},
}


def seam_entry(env: str) -> Optional[Dict]:
    for entry in PROGRAM_PAIRS:
        if entry["env"] == env:
            return entry
    return None


def test_exists(test_path: str) -> bool:
    """Registered tests resolve against the repo root (tools/ anchor),
    so analyzing a copied package tree still sees the real suite."""
    return os.path.exists(os.path.join(REPO_ROOT, test_path))
