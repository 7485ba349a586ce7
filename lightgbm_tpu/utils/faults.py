"""Fault-injection harness — named failure points for robustness tests.

The fork's headline features are survival features (periodic snapshots,
YARN re-rendezvous, retried socket sends — reference ``gbdt.cpp:309-327``,
``linkers_socket.cpp``); proving they work needs a way to MAKE the
failures happen on demand.  This module plants named injection points at
the seams where production faults actually strike:

* ``snapshot.write``   — mid-file during a snapshot write (power loss /
  preemption while serializing),
* ``collective.allgather`` — a cross-rank collective call (DCN blip),
* ``rendezvous.connect``   — the multi-host rendezvous handshake
  (coordinator not up yet),
* ``loader.read``      — opening a data file (flaky remote filesystem),
* ``spmd.skip_record`` — a collective site's flight-recorder fingerprint
  is silently dropped (simulating rank-divergent control flow that
  skips a collective; armed per-rank by the desync-localization tests —
  the fault is CAUGHT inside ``obs/flight_recorder.record``, it never
  propagates),
* ``serve.score``    — the serving harness's batched device dispatch
  (``serve/server.py``: a TPU worker restart mid-batch); retried by the
  shared policy, and the delivery contract (exactly-once per request)
  must hold across the retry,
* ``mem.leak``       — a SILENT fault (queried via :func:`fault_flag`,
  it never raises): while armed, the training loop appends one fresh
  device array per window into a module-lifetime sink
  (``boosting/gbdt.py``), simulating the live-buffer leak class the
  ``LGBM_TPU_MEM_CONTRACT=1`` watermark gate
  (``obs/mem_contract.py``) exists to catch,
* ``det.rng_drift``  — a SILENT fault: while armed, DART's keyed drop
  derivation (``boosting/variants.py``) consumes the NEXT iteration's
  draws instead of its own — simulating the RNG-divergence class
  (mis-keyed fold_in, stale seed plumbing) the determinism contract
  (``obs/determinism.py``, ``LGBM_TPU_DETERMINISM=1``) must catch by
  naming the first diverging eval window,
* ``watchdog.stall`` — a SILENT fault (``fault_flag``): while armed,
  the training window / serve batch currently armed on the stall
  watchdog (``obs/health.py``, ``LGBM_TPU_WATCHDOG_S``) sleeps
  in-window past the deadline — simulating the hung-dispatch class
  (wedged collective, dead runtime link) the watchdog must name in a
  ``health:stall`` event + kill-survivable forensic dump,
* ``health.nan_grad`` — a SILENT fault: while armed, one gradient
  element is poisoned to NaN (``boosting/gbdt._gradients``) —
  simulating the numerics-divergence class the window-boundary
  sentinels (``obs/health.py``) must catch with a ``health:nonfinite``
  event naming the window and a ``/healthz`` flip to ``degraded``,
* ``ingest.shard_fetch`` — the out-of-core shard ingest's per-shard
  source fetch (``io/outofcore.py``: the ``localize()`` download of a
  remote shard file — the fork's per-rank HDFS ``DownloadData``
  analog); retried by the shared policy, so a flaky remote FS is a
  transient, not a lost ingest,
* ``ingest.cache_write`` — mid-shard while appending binned blocks to
  the on-disk shard cache (power loss / preemption during ingest); the
  torn blob stays under its tmp name, the shard's sidecar is never
  published, and a re-run re-ingests exactly the unfinished shards —
  the manifest is written last, so a killed ingest can never be
  mistaken for a complete one,
* ``collective.hang`` — a SILENT fault (``fault_flag``): the host
  collective SLEEPS past ``LGBM_TPU_COLLECTIVE_DEADLINE_S`` instead of
  raising (``io/distributed.deadline_call``, elastic client
  allgathers) — exercising rank-loss *detection* (the deadline path
  must raise a typed ``RankLostError``), where ``collective.allgather``
  exercises retry,
* ``rendezvous.drop_rank`` — a SILENT fault: the elastic coordinator's
  monitor (``parallel/elastic.py``) evicts its newest member as if its
  heartbeats stopped — a lost rank without killing a process, so
  in-process tests drive generation bumps and survivor recovery,
* ``heartbeat.miss`` — a SILENT fault: the elastic client's heartbeat
  thread skips beats while armed; enough armed shots and the
  coordinator evicts the member (the dead-rank signal), few and the
  member survives (heartbeats are retried, not load-bearing
  one-shots),
* ``num.reassoc`` — a SILENT fault (``fault_flag``): while armed,
  ``learner/serial.py``'s ``root_stats`` swaps its canonical
  chunk+pairwise reduction back to a raw ``jnp.sum`` — reintroducing
  the exact PR 14 reassociation bug class so tests prove the identity
  harness (``tools/identity_check.py``) names the first diverging
  partition pair while the static gate (``tools/numcheck`` NUM001)
  flags the same hazard at file:line.  NOTE the flag is read ONCE at
  module import (host side — a traced-scope read would both be cached
  by jit and drag the faults machinery into detcheck's traced
  closure): arming is only effective in a fresh process (the harness
  re-execs an env-armed child),
* ``collective.slow`` — a SILENT fault: the elastic client sleeps
  ``LGBM_TPU_COLLECTIVE_SLOW`` seconds (default 0.25, clamped below
  the collective deadline) BEFORE entering the allgather — a straggler
  without a failure, under the sub-deadline threshold where
  ``collective.hang`` would trip rank loss; the fleet-observability
  tests use it to prove ``tools/fleet_report.py`` names the exact slow
  rank and site from wait/xfer accounting alone,
* ``stream.upload`` — the streamed trainer's per-block device upload
  (``boosting/streaming.py _upload_block``, the staging half of the
  upload/compute pipeline): retried by the shared policy BEFORE the
  block's fold is dispatched, so tests prove a transient device fault
  mid-pipeline is retried without a torn (double-counted or skipped)
  histogram fold.

Each point is a single ``fault_point(name)`` call that is a no-op unless
armed.  Tests arm points programmatically (:func:`inject`, or the
:func:`injected` context manager); operators can arm them from the
environment for chaos runs::

    LGBM_TPU_FAULTS="collective.allgather:2,rendezvous.connect:1"

fires the first 2 allgather calls and the first rendezvous attempt.
``name:times`` or ``name:times@skip`` (skip the first ``skip`` calls —
e.g. ``snapshot.write:1@1`` survives the first snapshot and dies inside
the second).  Injected failures raise :class:`FaultInjected`, whose
message carries the ``UNAVAILABLE`` transient marker so the retry layer
(``utils/retry.py``) classifies it exactly like a real RPC fault; arm
with ``!`` after the count (``name:1!``) for a NON-transient fault that
must pass straight through the retry layer.
"""
from __future__ import annotations

import os
from typing import Dict, Optional

POINTS = ("snapshot.write", "collective.allgather", "rendezvous.connect",
          "loader.read", "spmd.skip_record", "serve.score", "mem.leak",
          "det.rng_drift", "watchdog.stall", "health.nan_grad",
          "ingest.shard_fetch", "ingest.cache_write", "collective.hang",
          "rendezvous.drop_rank", "heartbeat.miss", "collective.slow",
          # sleeps while holding a contract-named lock
          # (obs/lock_contract.py): drives the contention-metric and
          # held-past-deadline paths in tests
          "lock.slow_hold",
          # swaps the canonical chunk+pairwise root reducer back to a
          # raw jnp.sum (learner/serial.py root_stats) — the PR 14
          # reassociation bug class
          "num.reassoc",
          # the streamed pipeline's per-block device_put
          # (boosting/streaming.py _upload_block): a transient device
          # fault mid-pipeline must retry BEFORE the fold dispatch, so
          # a retried upload can never tear a fold
          "stream.upload")


class FaultInjected(RuntimeError):
    """An injected fault.  ``transient`` controls whether the message
    carries the retry layer's transient marker."""

    def __init__(self, point: str, transient: bool = True):
        self.point = point
        self.transient = transient
        marker = "UNAVAILABLE" if transient else "PERMANENT"
        super().__init__(
            f"injected fault at {point!r} ({marker}: fault harness)")


class _Arm:
    __slots__ = ("times", "skip", "transient")

    def __init__(self, times: int, skip: int, transient: bool):
        self.times = times
        self.skip = skip
        self.transient = transient


def _named_lock(name: str):
    # lazy: utils.faults sits at the bottom of the import graph, and
    # lock_contract imports only the stdlib — cycle-free either way
    from ..obs.lock_contract import named_lock
    return named_lock(name)


_lock = _named_lock("faults")
_arms: Dict[str, _Arm] = {}
_fired: Dict[str, int] = {}
_calls: Dict[str, int] = {}
_env_loaded = False


def _load_env() -> None:
    global _env_loaded
    _env_loaded = True
    spec = os.environ.get("LGBM_TPU_FAULTS", "")
    for part in spec.split(","):
        part = part.strip()
        if not part or ":" not in part:
            continue
        name, rest = part.split(":", 1)
        transient = not rest.endswith("!")
        rest = rest.rstrip("!")
        skip = 0
        if "@" in rest:
            rest, skip_s = rest.split("@", 1)
            skip = int(skip_s)
        _arms[name.strip()] = _Arm(int(rest), skip, transient)


def inject(name: str, times: int = 1, skip: int = 0,
           transient: bool = True) -> None:
    """Arm ``name`` to fail its next ``times`` calls (after skipping the
    first ``skip``)."""
    with _lock:
        if not _env_loaded:
            _load_env()
        _arms[name] = _Arm(times, skip, transient)
        _fired.pop(name, None)
        _calls.pop(name, None)


def clear(name: Optional[str] = None) -> None:
    """Disarm one point, or everything (also resets counters)."""
    global _env_loaded
    with _lock:
        if name is None:
            _arms.clear()
            _fired.clear()
            _calls.clear()
            _env_loaded = True          # a full clear overrides the env
        else:
            _arms.pop(name, None)
            _fired.pop(name, None)
            _calls.pop(name, None)


def fired(name: str) -> int:
    """How many times ``name`` actually raised (for test assertions)."""
    with _lock:
        return _fired.get(name, 0)


def calls(name: str) -> int:
    """How many times ``name`` was reached, armed or not."""
    with _lock:
        return _calls.get(name, 0)


def fault_point(name: str) -> None:
    """The injection seam.  No-op unless ``name`` is armed; armed, it
    raises :class:`FaultInjected` for the configured number of calls."""
    with _lock:
        if not _env_loaded:
            _load_env()
        _calls[name] = _calls.get(name, 0) + 1
        arm = _arms.get(name)
        if arm is None:
            return
        if arm.skip > 0:
            arm.skip -= 1
            return
        if arm.times <= 0:
            return
        arm.times -= 1
        _fired[name] = _fired.get(name, 0) + 1
        transient = arm.transient
    from ..obs import counter_add, event
    counter_add(f"faults.{name}.fired")
    event("fault", name, transient=transient)
    raise FaultInjected(name, transient=transient)


def fault_flag(name: str) -> bool:
    """Non-raising variant of :func:`fault_point` for faults modeled as
    silent MISBEHAVIOR rather than errors (``mem.leak``): True when the
    armed point fires (consuming one shot, same counters/telemetry),
    False otherwise."""
    try:
        fault_point(name)
    except FaultInjected:
        return True
    return False


class injected:
    """``with injected("collective.allgather", times=2): ...`` — arms on
    entry, disarms (and forgets counters) on exit."""

    def __init__(self, name: str, times: int = 1, skip: int = 0,
                 transient: bool = True):
        self.name = name
        self.times = times
        self.skip = skip
        self.transient = transient

    def __enter__(self):
        inject(self.name, self.times, self.skip, self.transient)
        return self

    def __exit__(self, *exc):
        clear(self.name)
        return False
