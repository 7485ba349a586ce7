"""The declarative lock registry — concheck's ground truth.

Every lock in the package is declared here: which module/class owns
it, which mutable names it guards, and (as a DAG) the only order in
which locks may nest.  The static rules (``rules.py``) check the code
against these declarations; the runtime contract
(``obs/lock_contract.py``) gives the SAME lock names to its wrapped
locks, so a static CON002 finding and a runtime cycle report name the
same edge.

Declaration schema (one dict per lock)::

    {"name": "telemetry",                    # registry-wide unique id
     "module": "lightgbm_tpu/obs/telemetry.py",
     "cls": None,                            # owning class, None = module
     "attr": "_lock",                        # the variable holding it
     "kind": "rlock",                        # lock | rlock | condition
     "guards": ("_counters", ...),           # names only THIS lock guards
     "assume_held": ("_trace_write",)}       # helpers whose docstring
                                             # contract is "caller holds
                                             # the lock" — their writes
                                             # are treated as guarded

``ORDER`` declares the permitted nesting DAG as ``(outer, inner)``
edges; nesting is allowed along any DAG *path* (declared edges are
transitive), re-entry of the same rlock/condition is always allowed,
and everything else is a CON002.  Keep the DAG minimal: an edge is a
claim that holding ``outer`` while acquiring ``inner`` is deliberate.

``CALLBACKS`` names the user-supplied-callback seams (CON005): a call
through one of these names under a held lock is flagged unless the
entry carries a ``safe`` justification (which must argue the callback's
reachable set only ever takes declared-leaf locks).

Fixture/out-of-tree modules can declare the same facts in-file::

    CONCHECK_LOCKS = {"_lock": ("shared_counter",)}
    CONCHECK_ORDER = (("_lock_a", "_lock_b"),)
    CONCHECK_ASSUME_HELD = ("_helper",)
    CONCHECK_CALLBACKS = ("_callback",)

In-file lock names render as ``<basename>:<attr>``.
"""
from __future__ import annotations

from typing import Dict, Tuple

# ---------------------------------------------------------------------------
# locks
# ---------------------------------------------------------------------------
LOCKS: Tuple[Dict, ...] = (
    # -- telemetry: the per-process metrics spine ----------------------
    {"name": "telemetry", "module": "lightgbm_tpu/obs/telemetry.py",
     "cls": None, "attr": "_lock", "kind": "rlock",
     "guards": ("_enabled", "_trace_requested", "_trace_file",
                "_trace_open_path", "_spans", "_programs", "_counters",
                "_gauges", "_events", "_sections", "_held"),
     # "Caller holds _lock" is these helpers' documented contract
     "assume_held": ("_trace_write", "_program_row")},
    # MetricsRegistry is the telemetry SINK: leaf-level by design —
    # taken inside the telemetry lock on the write path (see ORDER)
    {"name": "metrics_registry", "module": "lightgbm_tpu/obs/ops_plane.py",
     "cls": "MetricsRegistry", "attr": "_lock", "kind": "lock",
     "guards": ("counters", "gauges", "events", "spans")},
    {"name": "ops_plane", "module": "lightgbm_tpu/obs/ops_plane.py",
     "cls": None, "attr": "_lock", "kind": "lock",
     "guards": ("_plane",)},
    {"name": "ops_drain", "module": "lightgbm_tpu/obs/ops_plane.py",
     "cls": "OpsPlane", "attr": "_hooks_lock", "kind": "lock",
     "guards": ("_drain_hooks",)},
    # -- health state machine + stall watchdog -------------------------
    {"name": "health", "module": "lightgbm_tpu/obs/health.py",
     "cls": None, "attr": "_lock", "kind": "rlock",
     "guards": ("_active", "_state")},
    {"name": "watchdog", "module": "lightgbm_tpu/obs/health.py",
     "cls": "Watchdog", "attr": "_cv", "kind": "condition",
     "guards": ("_armed", "_seq", "_stop")},
    # -- collective flight recorder ------------------------------------
    {"name": "flight_recorder",
     "module": "lightgbm_tpu/obs/flight_recorder.py",
     "cls": None, "attr": "_lock", "kind": "lock",
     "guards": ("_ring", "_count", "_digest")},
    # -- fleet accounting + the coordinator ledger ---------------------
    {"name": "fleet", "module": "lightgbm_tpu/obs/fleet.py",
     "cls": None, "attr": "_lock", "kind": "lock",
     "guards": ("_clock", "_seqs", "_skew", "_episodes")},
    {"name": "fleet_ledger", "module": "lightgbm_tpu/obs/fleet.py",
     "cls": "FleetLedger", "attr": "_wlock", "kind": "lock",
     "guards": ("_fd",)},
    # -- compile tracker (jax log handler runs on jax's threads) -------
    {"name": "trace_contract",
     "module": "lightgbm_tpu/obs/trace_contract.py",
     "cls": "CompileTracker", "attr": "_lock", "kind": "lock",
     "guards": ("_events", "_steady_idx")},
    # -- runtime lock contract's own graph lock (leaf everywhere) ------
    {"name": "lock_contract", "module": "lightgbm_tpu/obs/lock_contract.py",
     "cls": None, "attr": "_graph_lock", "kind": "lock",
     "guards": ("_edges", "_violations", "_stats")},
    # -- serving worker ------------------------------------------------
    {"name": "serve", "module": "lightgbm_tpu/serve/server.py",
     "cls": "PredictionServer", "attr": "_lock", "kind": "lock",
     "guards": ("_closed", "_n_submitted", "_n_resolved", "_n_failed",
                "_n_batches", "_n_rows", "_n_padded", "_latency")},
    # -- elastic coordinator + client ----------------------------------
    {"name": "elastic_coord", "module": "lightgbm_tpu/parallel/elastic.py",
     "cls": "ElasticCoordinator", "attr": "_cv", "kind": "condition",
     "guards": ("_members", "_generation", "_join_seq", "_rounds",
                "_reads", "_touch", "_arrivals", "_round_sites",
                "_gauge_ranks", "_deadline_hint", "_stop"),
     # "Caller holds _cv" helpers (documented in their docstrings)
     "assume_held": ("_bump", "_ranks", "_view")},
    {"name": "elastic_client", "module": "lightgbm_tpu/parallel/elastic.py",
     "cls": "ElasticClient", "attr": "_state_lock", "kind": "lock",
     "guards": ("_seen_generation",)},
    # -- fault harness + log dedupe (leaf utility locks) ---------------
    {"name": "faults", "module": "lightgbm_tpu/utils/faults.py",
     "cls": None, "attr": "_lock", "kind": "lock",
     "guards": ("_arms", "_fired", "_calls", "_env_loaded"),
     "assume_held": ("_load_env",)},
    {"name": "log_once", "module": "lightgbm_tpu/utils/log.py",
     "cls": None, "attr": "_once_lock", "kind": "lock",
     "guards": ("_once_seen",)},
)

# ---------------------------------------------------------------------------
# the permitted nesting DAG: (outer, inner).  Nesting along any DAG
# path is legal; an acquisition pair with no path is CON002.
# ---------------------------------------------------------------------------
ORDER: Tuple[Tuple[str, str], ...] = (
    # telemetry mirrors every update into the sink while holding its
    # own lock; MetricsRegistry's lock is the declared leaf under it
    ("telemetry", "metrics_registry"),
    # ops_plane.mount()/shutdown() construct/tear down the plane under
    # the module lock: OpsPlane.__init__ enables telemetry and flips
    # health; both inner locks nest under the mount lock
    ("ops_plane", "telemetry"),
    ("ops_plane", "health"),
    # a failed mount logs the degradation while still under the module
    # lock; log_once's dedupe lock is a leaf
    ("ops_plane", "log_once"),
    # health._set_active holds the (reentrant) health lock through
    # _transition, whose tail publishes the section via telemetry
    ("health", "telemetry"),
    # the coordinator emits telemetry/ledger lines and polls fault
    # flags from inside its condition variable (monitor + op handlers)
    ("elastic_coord", "telemetry"),
    ("elastic_coord", "fleet_ledger"),
    ("elastic_coord", "faults"),
    ("elastic_coord", "log_once"),
    # every wrapped lock may report wait/hold samples into the contract
    # graph; the graph lock is a declared leaf under all of them
    ("telemetry", "lock_contract"),
    ("metrics_registry", "lock_contract"),
    ("elastic_coord", "lock_contract"),
)

# ---------------------------------------------------------------------------
# user-supplied callback seams (CON005)
# ---------------------------------------------------------------------------
CALLBACKS: Tuple[Dict, ...] = (
    # telemetry.set_sink installs an arbitrary object whose methods run
    # under the telemetry lock.  Safe ONLY because the one sanctioned
    # sink (MetricsRegistry) takes nothing but its declared-leaf lock;
    # tests/test_lock_contract.py pins the re-entrancy contract.
    {"module": "lightgbm_tpu/obs/telemetry.py", "name": "sink",
     "safe": "MetricsRegistry methods take only the declared-leaf "
             "metrics_registry lock (ORDER edge telemetry -> "
             "metrics_registry); re-entrancy pinned by "
             "tests/test_lock_contract.py"},
)
