"""One-command cross-partition identity harness:
``python -m tools.identity_check``.

The executable form of the byte-identity contract (PR 11/14/16):
training is a pure function of ``(data, config, S)`` where ``S`` is
the protocol shard count — NEVER of how those shards are scheduled,
fused, streamed, or which members computed them.  One toy workload is
trained across the full partition matrix and the digest law is
asserted within each shard-count group:

* ``S=1`` — ``serial`` (in-memory fused path) and ``stream1`` (the
  streamed trainer over the same resident bytes);
* ``S=2`` — ``mesh2`` (in-memory 2-shard data-parallel mesh),
  ``mesh2_block0`` (the same mesh under the ``LGBM_TPU_MESH_BLOCK=0``
  per-iteration escape hatch), ``stream2`` (streamed 2-shard), and
  ``elastic1`` (the elastic protocol at world 1 pinned to ``S=2``);
* ``S=1·pallas`` — the ISSUE 20 streamed-kernel group:
  ``serial_pallas`` (in-memory monolithic kernel) vs
  ``stream1_pallas`` (accumulator-seeded per-block kernel folds),
  both force-run on CPU through the auto-interpret path.  A SEPARATE
  group: the quantized kernel histograms legitimately differ in
  value from the exact scatter backend, so the law is identity
  within a forced backend, never across backends.

(Serial and 2-shard models legitimately differ: per-shard partials
combine through the psum seam in a different — but partition-pinned —
order.  The law is identity WITHIN a shard count, which is exactly
what elastic recovery and streamed restarts rely on.)

Every scenario runs with the determinism ledger armed
(``LGBM_TPU_DETERMINISM=1``); a violation is reported as the FIRST
diverging scenario pair and window, the localization a real
reassociation bug needs.  The ulp contract
(``LGBM_TPU_NUM_CONTRACT=1``, ``obs/num_contract.py``) rides along:
any window whose canonical-vs-f64-oracle drift trips the registered
``score_root_ulp`` budget fails the run too.

``--drift-proof`` proves the wall trips on the PR 14 bug class: a
child process re-execs the ``S=1`` group with the ``num.reassoc``
fault armed from the environment (``utils/faults.py`` — the canonical
chunk+pairwise root reducer silently reverts to a raw ``jnp.sum``;
env-armed because jit resolves the flag at trace time).  The fused
in-memory program and the streamed per-block programs then accumulate
in different orders, the digest law breaks, and the harness must exit
nonzero naming the diverging pair — while ``tools/numcheck``'s NUM001
flags the same hazard statically at file:line.

Usage::

    python -m tools.identity_check [--scenarios serial,stream1,...]
                                   [--rows 600] [--rounds 6]
                                   [--drift-proof] [--json]
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
from typing import Dict, List, Optional, Tuple

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("LGBM_TPU_DETERMINISM", "1")
os.environ.setdefault("LGBM_TPU_NUM_CONTRACT", "1")
if "host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    # the whole matrix runs in ONE process: the mesh scenarios need a
    # 2-device pool, fixed before jax initializes
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=2"
                               ).strip()

import numpy as np

# scenario -> shard-count group; identity is asserted WITHIN a group
MATRIX: Dict[str, str] = {
    "serial": "S=1",
    "stream1": "S=1",
    "mesh2": "S=2",
    "mesh2_block0": "S=2",
    "stream2": "S=2",
    "elastic1": "S=2",
    "serial_pallas": "S=1·pallas",
    "stream1_pallas": "S=1·pallas",
}

BASE_PARAMS = {"objective": "binary", "num_leaves": 7,
               "min_data_in_leaf": 5, "verbose": -1, "output_freq": 2,
               "learning_rate": 0.2}


def _toy_data(rows: int, f: int = 6, seed: int = 7):
    """Synthetic binary data, pure in ``seed`` (counter-based Philox —
    the harness itself must satisfy its own contract)."""
    gen = np.random.Generator(np.random.Philox(key=[seed, 0]))
    X = gen.normal(size=(rows, f)).astype(np.float32)
    noise = np.random.Generator(np.random.Philox(key=[seed, 1])).normal(
        size=rows)
    y = (X[:, 0] + 0.5 * noise > 0).astype(np.float64)
    return X, y


def _resident(X, y, params):
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import BinnedDataset, Metadata
    cfg = Config.from_params(dict(params))
    md = Metadata()
    md.set_field("label", y)
    return cfg, BinnedDataset.from_raw(X, cfg, metadata=md)


def run_once(scenario: str, rows: int, rounds: int) -> Dict:
    """Train one scenario; -> {"ledger": {window_it: digest}, "final":
    digest, "num_trips": [...], "num_ledger": [...]}."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu.boosting.streaming import (StreamTrainer,
                                                 train_elastic)
    from lightgbm_tpu.obs import determinism, num_contract
    determinism.reset()
    num_contract.reset()
    X, y = _toy_data(rows)
    params = {**BASE_PARAMS, "num_iterations": rounds}
    # ISSUE 20 streamed-kernel scenarios: "<base>_pallas" forces the
    # histogram backend on BOTH sides of the pair (env save/restored)
    base, fenv = scenario, {}
    if scenario.endswith("_pallas"):
        base = scenario[:-len("_pallas")]
        fenv = {"LGBM_TPU_HIST_BACKEND": "pallas"}
    saved = {k: os.environ.get(k) for k in fenv}
    os.environ.update(fenv)
    try:
        return _run_base(base, scenario, X, y, params, fenv)
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def _run_base(base: str, scenario: str, X, y, params, fenv) -> Dict:
    import lightgbm_tpu as lgb
    from lightgbm_tpu.boosting.streaming import (StreamTrainer,
                                                 train_elastic)
    from lightgbm_tpu.obs import determinism, num_contract
    if base in ("mesh2", "mesh2_block0"):
        params.update({"tree_learner": "data", "mesh_shape": [2]})
    if base in ("serial", "mesh2", "mesh2_block0"):
        block0 = base == "mesh2_block0"
        old = os.environ.get("LGBM_TPU_MESH_BLOCK")
        if block0:
            os.environ["LGBM_TPU_MESH_BLOCK"] = "0"
        try:
            gbdt = lgb.train(params, lgb.Dataset(X, label=y,
                                                 params=params))._gbdt
        finally:
            if block0:
                if old is None:
                    os.environ.pop("LGBM_TPU_MESH_BLOCK", None)
                else:
                    os.environ["LGBM_TPU_MESH_BLOCK"] = old
    elif base in ("stream1", "stream2"):
        cfg, res = _resident(X, y, params)
        shards = 2 if base == "stream2" else 0
        tr = StreamTrainer(cfg, res, num_shards=shards)
        if fenv:
            assert tr.backend == fenv["LGBM_TPU_HIST_BACKEND"], \
                f"{scenario}: forced backend not engaged ({tr.backend})"
        gbdt = tr.train()
    elif base == "elastic1":
        from lightgbm_tpu.parallel.elastic import (ElasticClient,
                                                   ElasticCoordinator)
        cfg, res = _resident(X, y, params)
        coord = ElasticCoordinator(heartbeat_timeout_s=10.0)
        coord.start()
        try:
            client = ElasticClient(coord.address, member="ident0",
                                   deadline_s=10.0,
                                   heartbeat_interval_s=0.1)
            gbdt = train_elastic(params, res, num_shards=2,
                                 client=client)
            client.leave()
            client.close()
        finally:
            coord.stop()
    else:
        raise ValueError(f"unknown scenario {scenario!r}")
    ledger = {int(it): d for it, d in determinism.section()["digests"]}
    return {"ledger": ledger, "final": gbdt.digest(),
            "num_trips": num_contract.trips(),
            "num_ledger": num_contract.ledger()}


def first_pair_divergence(ref_name: str, ref: Dict, name: str, got: Dict
                          ) -> Optional[str]:
    """The failure message for the FIRST diverging (pair, window), or
    None when the pair satisfies the digest law.  Window ledgers are
    compared on COMMON iterations (partitionings sample on different
    window grids: the fused mesh once per fusion block, the streamed
    trainer every iteration)."""
    common = sorted(set(ref["ledger"]) & set(got["ledger"]))
    for it in common:
        if ref["ledger"][it] != got["ledger"][it]:
            return (f"first diverging pair ({ref_name}, {name}) at "
                    f"window it={it}: {ref['ledger'][it][:12]} vs "
                    f"{got['ledger'][it][:12]}")
    if ref["final"] != got["final"]:
        return (f"first diverging pair ({ref_name}, {name}) at final "
                f"model: {ref['final'][:12]} vs {got['final'][:12]}")
    return None


def check_matrix(scenarios: List[str], rows: int, rounds: int
                 ) -> Tuple[bool, List[str]]:
    results = {s: run_once(s, rows, rounds) for s in scenarios}
    ok = True
    lines: List[str] = []
    for group in dict.fromkeys(MATRIX[s] for s in scenarios):
        members = [s for s in scenarios if MATRIX[s] == group]
        ref = members[0]
        group_ok = True
        for other in members[1:]:
            msg = first_pair_divergence(ref, results[ref], other,
                                        results[other])
            if msg is not None:
                ok = group_ok = False
                lines.append(f"{group}: FAIL — {msg}")
        if group_ok:
            lines.append(f"{group}: OK — {len(members)} partitioning(s) "
                         f"byte-identical "
                         f"({results[ref]['final'][:12]})")
    for s in scenarios:
        for trip in results[s]["num_trips"]:
            ok = False
            lines.append(f"{s}: FAIL — ulp budget trip at window "
                         f"it={trip['window_it']} "
                         f"({trip['drift_ulps']} ulps > "
                         f"{trip['budget']})")
    return ok, lines


def drift_proof(rows: int, rounds: int) -> Tuple[bool, str]:
    """The wall must TRIP: re-exec the S=1 pair in a child with the
    ``num.reassoc`` fault armed from the environment (trace-time flag:
    arming in THIS process would miss already-compiled programs); the
    child must exit nonzero naming a diverging pair."""
    env = dict(os.environ)
    env["LGBM_TPU_FAULTS"] = "num.reassoc:1000000"
    env.pop("XLA_FLAGS", None)        # child re-derives its own pool
    proc = subprocess.run(
        [sys.executable, "-m", "tools.identity_check", "--scenarios",
         "serial,stream1", "--rows", str(rows), "--rounds",
         str(rounds)],
        env=env, capture_output=True, text=True,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    named = [ln for ln in proc.stdout.splitlines()
             if "first diverging pair" in ln]
    if proc.returncode == 0 or not named:
        return False, ("drift-proof: FAIL — num.reassoc armed but the "
                       "identity matrix passed: the harness is blind "
                       "to the PR 14 bug class (child rc="
                       f"{proc.returncode})")
    return True, (f"drift-proof: OK — reassociated root reducer "
                  f"localized ({named[0].strip()})")


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m tools.identity_check",
        description="cross-partition byte-identity harness (the "
                    "runtime half of numcheck)")
    parser.add_argument("--scenarios", default=",".join(MATRIX))
    parser.add_argument("--rows", type=int, default=600)
    parser.add_argument("--rounds", type=int, default=6)
    parser.add_argument("--drift-proof", action="store_true",
                        help="also prove num.reassoc breaks the digest "
                             "law and is named")
    parser.add_argument("--json", action="store_true",
                        help="emit one machine-readable JSON line")
    args = parser.parse_args(argv)

    wanted = [s.strip() for s in args.scenarios.split(",") if s.strip()]
    bad = [s for s in wanted if s not in MATRIX]
    if bad:
        print(f"identity_check: unknown scenario(s) {bad}",
              file=sys.stderr)
        return 2

    ok, lines = check_matrix(wanted, args.rows, args.rounds)
    for ln in lines:
        print(ln)
    proof_ok = True
    if args.drift_proof:
        proof_ok, msg = drift_proof(args.rows, args.rounds)
        print(msg)
    if args.json:
        print(json.dumps({"identity_check_ok": bool(ok and proof_ok),
                          "scenarios": wanted}))
    if not (ok and proof_ok):
        print("identity_check: FAIL")
        return 1
    print(f"identity_check: ok ({len(wanted)} partitioning(s), "
          f"digest law holds per shard count)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
