"""Structured training telemetry: spans, counters, gauges, JSONL traces.

The reference prints only eval lines; on a TPU mesh that leaves every
production question — where does wall-clock go (compile vs steady
state, histogram vs split vs routing, collective vs compute), did the
run degrade (kernel fallback, retries, injected faults), what did the
snapshot machinery cost — unanswerable.  This module is the phase-level
accounting the LightGBM paper used to justify its histogram design
(Ke et al., NeurIPS 2017, Table 2), grown into a run-queryable
subsystem:

* **Spans** — ``with span("tree_build") as s: ...; s["bytes"] = n``.
  Host-side wall-clock only, nestable (thread-local stack), NO implicit
  device syncs: a span around an async JAX dispatch times the host cost
  of that dispatch; callers that want device time must block first (the
  jit-adjacent block-loop boundaries already do).  Every span also
  enters a ``jax.profiler.TraceAnnotation`` of its name, so a profiler
  trace, whoever started it, holds the spans on the device's clock
  (device time is read there, by ``jax.named_scope`` name: the block
  program's scopes carry the ``tree.*`` / ``obj.*`` span names).
  A span's **self time** is its duration less what its direct children
  on the same thread took: what lies under none of their names.
* **Compile record** — while telemetry is enabled, every program JAX
  traces, lowers or compiles is a span (``compile.trace`` /
  ``compile.lower`` / ``compile.backend``, attribute ``fun_name``)
  under whatever span is open on the compiling thread, and a row of the
  summary's ``programs`` table (see :func:`_compile_end`).
* **Counters / gauges** — ``counter_add("retry.dispatch.retries")``,
  ``gauge_set("hbm_bytes", n)``.  Counters accumulate (floats allowed:
  backoff seconds ride the same channel), gauges overwrite.
* **Events** — one-shot occurrences (``event("fault", name)``: an
  injection fired, early stopping triggered).

Sinks:

* an in-memory **run summary** queryable as a plain dict
  (:func:`summary`): per-span count/total/max/self seconds, the
  ``programs`` table, counters, gauges, event counts;
* a **JSONL event trace**, enabled via ``LGBM_TPU_TRACE=<path>`` or the
  ``telemetry_output`` config parameter.  Every record carries ``ts``
  (wall-clock start, epoch seconds), ``kind`` (``span`` | ``counter`` |
  ``gauge`` | ``event``), ``name``, and ``rank``; span records add
  ``dur_s`` (>= 0), ``self_s``, ``depth``, and ``parent`` — spans are written on
  CLOSE, so a parent's record follows its children's;
* **per-rank files** in multi-host runs (the trace path gains a
  ``.rank<k>`` suffix, decided lazily at first write so enabling before
  ``jax.distributed.initialize`` still lands per-rank) with a rank-0
  **merged summary** over the existing host-collective path
  (:func:`merged_summary` + ``io/distributed.jax_process_allgather``).

Disabled telemetry is a guard-checked no-op — one module-attribute read
per call site — so instrumentation stays compiled into every path,
including per-iteration training loops and per-feature bin finding, and
nothing is registered with ``jax.monitoring`` until :func:`enable`.
"""
from __future__ import annotations

import importlib
import json
import os
import sys
import threading
import time
from typing import Any, Dict, IO, Optional

__all__ = [
    "enabled", "enable", "disable", "reset", "span", "counter_add",
    "gauge_set", "event", "summary", "merged_summary", "write_summary",
    "trace_path", "set_section", "set_sink",
    "set_clock_offset", "set_rank",
]

def _named_rlock(name: str):
    # lazy: lock_contract imports only stdlib, so this is cycle-free
    from . import lock_contract
    return lock_contract.named_rlock(name)


_lock = _named_rlock("telemetry")
_tls = threading.local()            # per-thread stack of open spans

# -- state (module-level flags keep the disabled path one attribute read)
_enabled = False
_trace_requested: Optional[str] = None   # path asked for; file opens lazily
_trace_file: Optional[IO[str]] = None
_trace_open_path: Optional[str] = None

_spans: Dict[str, list] = {}        # name -> [count, total_s, max_s, self_s]
# fun_name -> {"count", "trace_s", "lower_s", "backend_s"}: see _compile_end
_programs: Dict[str, Dict[str, float]] = {}
_PROGRAMS_MAX = 256                 # names; the rest share "(other)"
# jax.monitoring's names of the three compile phases -> (span, column)
_COMPILE_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": ("compile.trace", "trace_s"),
    "/jax/core/compile/jaxpr_to_mlir_module_duration":
        ("compile.lower", "lower_s"),
    "/jax/core/compile/backend_compile_duration":
        ("compile.backend", "backend_s"),
}
_CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "compile.cache_hits",
    "/jax/compilation_cache/cache_misses": "compile.cache_misses",
}
_listening = False                  # the listeners below are registered
_counters: Dict[str, float] = {}
_gauges: Dict[str, Any] = {}
_events: Dict[str, int] = {}
# named summary sections (e.g. "trace_contract"): written by subsystems
# that produce one structured result per run rather than a stream;
# stored even while telemetry is disabled — a contract check the user
# explicitly enabled must not vanish because tracing is off
_sections: Dict[str, Any] = {}
# live-metrics sink (obs/ops_plane.py MetricsRegistry): while the ops
# plane is mounted, every counter/gauge/event update and span close is
# mirrored into the scrapeable registry.  None (the default) costs one
# module-attribute read on the already-enabled path; the disabled path
# never reaches it — the PR 2 no-op envelope is untouched
_sink = None
# coordinator-clock offset of this rank (obs/fleet.py): when set, every
# trace record carries it as `clk_off_s` so tools/fleet_report.py can
# merge per-rank traces onto one clock (corrected_ts = ts + clk_off_s).
# None (the default) adds nothing — single-host traces are unchanged
_clk_off: Optional[float] = None
# (rank, world) override for fleets that are NOT a jax multi-process
# world (elastic workers: each is a world-1 jax process, but the
# ELASTIC rank/world decide trace-file suffixes and summary identity)
_rank_override = None


def set_clock_offset(offset_s: Optional[float]) -> None:
    """Install this rank's coordinator-clock offset (``obs/fleet.py``
    owns the estimation); ``None`` removes the stamp."""
    global _clk_off
    _clk_off = None if offset_s is None else float(offset_s)


def set_rank(rank: int, world: int) -> None:
    """Override the (rank, world) identity used for trace-record rank
    stamps, per-rank trace-file suffixes, and summaries.  Elastic
    training calls this after join/resync — its ranks come from the
    coordinator, not from jax.distributed."""
    global _rank_override
    _rank_override = (int(rank), max(int(world), 1))


def set_sink(sink) -> None:
    """Install/remove the live-metrics sink (counter/gauge/event/span
    callbacks).  Owned by ``obs/ops_plane.py``; survives :func:`reset`
    — the plane's lifecycle is the process, not one run."""
    global _sink
    _sink = sink


def get_sink():
    """The installed sink or None.  Lock-free single attribute read —
    ``obs/lock_contract.py`` calls this from inside lock wrappers, so
    it must never take the telemetry lock."""
    return _sink


def _rank_world():
    """(rank, world) without initializing any jax backend: reads the
    distributed client state only when jax is already imported (the
    same best-effort probe the CLI's already-meshed check uses).  An
    elastic :func:`set_rank` override wins — those workers are world-1
    jax processes whose fleet identity lives with the coordinator."""
    if _rank_override is not None:
        return _rank_override
    jx = sys.modules.get("jax")
    if jx is None:
        return 0, 1
    try:
        from jax._src import distributed
        st = distributed.global_state
        if getattr(st, "client", None) is None:
            return 0, 1
        return int(st.process_id or 0), int(st.num_processes or 1)
    # tpulint: disable=TPL006 -- best-effort probe of private jax state
    except Exception:                   # noqa: BLE001 - probe is best-effort
        return 0, 1


# ---------------------------------------------------------------------------
# lifecycle
# ---------------------------------------------------------------------------
def enabled() -> bool:
    return _enabled


def enable(trace_path: Optional[str] = None) -> None:
    """Turn telemetry on.  ``trace_path`` additionally streams every
    record as one JSON line (appended; per-rank suffix in multi-host
    runs).  Idempotent; a second call can add a trace to an already
    enabled run."""
    global _enabled, _trace_requested
    with _lock:
        _enabled = True
        if trace_path:
            _trace_requested = trace_path
    _listen()


def disable() -> None:
    """Turn telemetry off (the accumulated summary is kept)."""
    global _enabled, _trace_file, _trace_open_path
    with _lock:
        _enabled = False
        if _trace_file is not None:
            try:
                _trace_file.close()
            except OSError:
                pass
        _trace_file = None
        _trace_open_path = None


def reset() -> None:
    """Clear the run summary and forget any requested trace (tests).
    Also rewinds the collective flight recorder — a fresh run must not
    inherit the previous run's schedule digest."""
    global _trace_requested, _held, _clk_off, _rank_override
    with _lock:
        disable()
        _trace_requested = None
        _held = None
        _clk_off = None
        _rank_override = None
        _spans.clear()
        _programs.clear()
        _counters.clear()
        _gauges.clear()
        _events.clear()
        _sections.clear()
        if getattr(_tls, "stack", None):
            _tls.stack = []
    _unlisten()
    from . import flight_recorder
    flight_recorder.reset()
    from . import profiler
    profiler.reset()
    from . import health
    health.reset()
    from . import fleet
    fleet.reset()


def trace_path() -> Optional[str]:
    """The trace file path actually written to (with any rank suffix),
    or the requested path when nothing has been written yet."""
    return _trace_open_path or _trace_requested


def _init_from_env() -> None:
    path = os.environ.get("LGBM_TPU_TRACE", "")
    if path:
        enable(trace_path=path)


# ---------------------------------------------------------------------------
# trace writing
# ---------------------------------------------------------------------------
_held = None                  # not None => buffer records instead of writing


def hold_trace() -> None:
    """Buffer trace records in memory instead of opening the trace
    file.  Called around the multi-host rendezvous
    (``parallel/mesh.init_distributed``): records emitted DURING the
    rendezvous (its own retry counters) must not open the trace file
    before the process knows its rank — every rank would grab the same
    unsuffixed path.  No-op when already holding."""
    global _held
    with _lock:
        if _held is None:
            _held = []


def release_trace() -> None:
    """Flush records buffered by :func:`hold_trace` (their ``rank``
    field is re-stamped — it was unknowable at emission) and resume
    direct writes."""
    global _held
    with _lock:
        pending, _held = _held, None
        if pending:
            rank, _ = _rank_world()
            for rec in pending:
                rec["rank"] = rank
                _trace_write(rec)


def _trace_write(record: Dict[str, Any]) -> None:
    """Append one JSONL record.  Caller holds ``_lock``.  The file
    opens lazily so multi-host runs that enable telemetry before
    ``jax.distributed.initialize`` still get per-rank files."""
    global _trace_file, _trace_open_path
    if _clk_off is not None and "clk_off_s" not in record:
        record["clk_off_s"] = _clk_off
    if _held is not None:
        _held.append(record)
        return
    if _trace_file is None:
        if not _trace_requested:
            return
        rank, world = _rank_world()
        path = _trace_requested
        if world > 1:
            path = f"{path}.rank{rank}"
        try:
            _trace_file = open(path, "a", buffering=1)
            _trace_open_path = path
        except OSError:
            return
    try:
        _trace_file.write(json.dumps(record) + "\n")
    except (OSError, ValueError):
        pass


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------
class _Discard:
    """Attr sink for the disabled path: swallows writes, costs nothing."""
    __slots__ = ()

    def __setitem__(self, key, value):
        pass

    def update(self, *args, **kwargs):
        pass


_DISCARD = _Discard()


class _NoopSpan:
    __slots__ = ()

    def __enter__(self):
        return _DISCARD

    def __exit__(self, *exc):
        return False


_NOOP_SPAN = _NoopSpan()


def _program_row(fun: str) -> Dict[str, float]:
    """The ``programs`` table's row of ``fun``, made where it is new;
    caller holds ``_lock``."""
    row = _programs.get(fun)
    if row is None:
        if len(_programs) >= _PROGRAMS_MAX:
            fun = "(other)"
        row = _programs.setdefault(fun, {
            "count": 0, "trace_s": 0.0, "lower_s": 0.0, "backend_s": 0.0})
    return row


class _Span:
    __slots__ = ("name", "attrs", "t0", "ts", "depth", "ann", "children_s")

    def __init__(self, name: str, attrs: Dict[str, Any]):
        self.name = name
        self.attrs = attrs

    def _open(self) -> None:
        """Onto this thread's stack, and onto the profiler's clock,
        whoever started the trace: a TraceAnnotation costs tens of
        nanoseconds while no profiler session is live.  A process that
        never imported jax has no session to annotate and compiles
        nothing."""
        stack = getattr(_tls, "stack", None)
        if stack is None:
            stack = _tls.stack = []
        self.depth = len(stack)
        self.children_s = 0.0
        stack.append(self)
        self.ann = None
        jx = sys.modules.get("jax")
        if jx is not None:
            if not _listening:
                _listen()
            try:
                ann = jx.profiler.TraceAnnotation(self.name)
                ann.__enter__()
                self.ann = ann
            # tpulint: disable=TPL006 -- annotation is best-effort; a
            # profiler hiccup must not take the training span down
            except Exception:           # noqa: BLE001
                pass

    def _leave(self, exc) -> None:
        if self.ann is not None:
            try:
                self.ann.__exit__(*exc)
            # tpulint: disable=TPL006 -- annotation close is best-effort
            except Exception:           # noqa: BLE001
                pass
            self.ann = None

    def _close(self, dur: float, exc=(None, None, None),
               column: Optional[str] = None) -> None:
        """Off the stack (with whatever a compile phase that never
        ended left above), the parent credited, the summary and the
        trace written.  ``column``: see :func:`_compile_end`."""
        self._leave(exc)
        stack = getattr(_tls, "stack", None) or []
        if any(f is self for f in stack):
            while stack[-1] is not self:
                stack.pop()._leave(exc)
            stack.pop()
        parent = ""
        if stack:
            stack[-1].children_s += dur
            parent = stack[-1].name
        self_s = max(dur - self.children_s, 0.0)
        rank, _ = _rank_world()
        with _lock:
            agg = _spans.get(self.name)
            if agg is None:
                agg = _spans[self.name] = [0, 0.0, 0.0, 0.0]
            agg[0] += 1
            agg[1] += dur
            if dur > agg[2]:
                agg[2] = dur
            agg[3] += self_s
            if column is not None:
                _program_row(self.attrs["fun_name"])[column] += dur
            sink = _sink
            if sink is not None:
                sink.span(self.name, dur)
            if _trace_requested:
                rec = {"ts": self.ts, "kind": "span", "name": self.name,
                       "rank": rank, "dur_s": dur, "self_s": self_s,
                       "depth": self.depth, "parent": parent}
                if self.attrs:
                    rec.update(self.attrs)
                _trace_write(rec)

    def __enter__(self):
        self._open()
        self.ts = time.time()
        self.t0 = time.perf_counter()
        return self.attrs

    def __exit__(self, *exc):
        self._close(time.perf_counter() - self.t0, exc)
        return False


class _Phase(_Span):
    """A compile phase as JAX reports it; ``nested``: inside another
    phase on its thread."""
    __slots__ = ("nested",)


def span(name: str, **attrs):
    """Context manager timing the enclosed block under ``name``; yields
    a dict the block may add fields to (they land on the trace record).
    A shared no-op when telemetry is disabled."""
    if not _enabled:
        return _NOOP_SPAN
    return _Span(name, attrs)


# ---------------------------------------------------------------------------
# the compile record
# ---------------------------------------------------------------------------
def _listen() -> None:
    """Register the three listeners with ``jax.monitoring``, once, and
    only where jax is already imported (``_rank_world``'s rule: this
    module never imports it); :func:`enable` before ``import jax``
    leaves it to the first span that finds jax."""
    global _listening
    if _listening or not _enabled or "jax" not in sys.modules:
        return
    with _lock:
        if _listening:
            return
        mon = importlib.import_module("jax.monitoring")
        mon.register_scalar_listener(_compile_start)
        mon.register_event_duration_secs_listener(_compile_end)
        mon.register_event_listener(_cache_event)
        _listening = True


def _unlisten() -> None:
    global _listening
    with _lock:
        if not _listening:
            return
        mon = sys.modules["jax.monitoring"]
        mon.unregister_scalar_listener(_compile_start)
        mon.unregister_event_duration_listener(_compile_end)
        mon.unregister_event_listener(_cache_event)
        _listening = False


def _open_phase(name: str, ts: float) -> "_Phase":
    phase = _Phase(name, {})
    phase.nested = any(isinstance(f, _Phase)
                       for f in getattr(_tls, "stack", None) or ())
    phase._open()
    phase.ts = ts
    return phase


def _compile_start(event: str, start_time: float, **kw) -> None:
    """JAX's ``record_scalar`` at the entry of a compile phase: open a
    span on the compiling thread."""
    if _enabled and event in _COMPILE_PHASES:
        _open_phase(_COMPILE_PHASES[event][0], start_time)


def _compile_end(event: str, dur: float, **kw) -> None:
    """JAX's duration at the exit of a compile phase: close the span
    :func:`_compile_start` opened, a child of whatever is open beneath
    it on this thread.

    The ``programs`` table holds a row per program: the ``fun_name`` of
    a phase that runs inside no other phase on its thread (lowering
    says ``jit(f)`` where tracing says ``f``: the row is ``f``).  Its
    ``trace_s`` / ``lower_s`` / ``backend_s`` are those phases whole: a
    jit traced inside the trace or the lowering of another is part of
    the program it is traced into and has no row, so no second stands
    in two rows.  ``count`` is XLA compilations, nested ones too."""
    if not _enabled or event not in _COMPILE_PHASES:
        return
    name, column = _COMPILE_PHASES[event]
    phase = next((f for f in reversed(getattr(_tls, "stack", None) or ())
                  if isinstance(f, _Phase) and f.name == name), None)
    if phase is None:
        # enabled in the middle of the phase: a leaf, under what is open
        phase = _open_phase(name, time.time() - dur)
    fun = str(kw.get("fun_name", "?"))
    if fun.startswith("jit(") and fun.endswith(")"):
        fun = fun[4:-1]
    phase.attrs["fun_name"] = fun
    if column == "backend_s":
        with _lock:
            _program_row(fun)["count"] += 1
    phase._close(float(dur), column=None if phase.nested else column)


def _cache_event(event: str, **kw) -> None:
    name = _CACHE_EVENTS.get(event)
    if name is not None:
        counter_add(name)


# ---------------------------------------------------------------------------
# counters / gauges / events
# ---------------------------------------------------------------------------
def counter_add(name: str, n: float = 1) -> None:
    if not _enabled:
        return
    rank, _ = _rank_world()
    with _lock:
        _counters[name] = _counters.get(name, 0) + n
        sink = _sink
        if sink is not None:
            sink.counter(name, n, _counters[name])
        if _trace_requested:
            _trace_write({"ts": time.time(), "kind": "counter",
                          "name": name, "rank": rank, "add": n,
                          "value": _counters[name]})


def gauge_set(name: str, value: Any) -> None:
    if not _enabled:
        return
    rank, _ = _rank_world()
    with _lock:
        _gauges[name] = value
        sink = _sink
        if sink is not None:
            sink.gauge(name, value)
        if _trace_requested:
            _trace_write({"ts": time.time(), "kind": "gauge",
                          "name": name, "rank": rank, "value": value})


def event(kind: str, name: str, **fields) -> None:
    """Record a one-shot occurrence.  ``kind`` is a coarse family
    (``"fault"``, ``"early_stop"``, ...) kept distinct from the three
    structural kinds; the trace record's ``kind`` field is ``"event"``
    with the family under ``"family"``."""
    if not _enabled:
        return
    rank, _ = _rank_world()
    with _lock:
        key = f"{kind}:{name}"
        _events[key] = _events.get(key, 0) + 1
        sink = _sink
        if sink is not None:
            sink.event(key, _events[key])
        if _trace_requested:
            rec = {"ts": time.time(), "kind": "event", "name": name,
                   "rank": rank, "family": kind}
            if fields:
                rec.update(fields)
            _trace_write(rec)


# ---------------------------------------------------------------------------
# summaries
# ---------------------------------------------------------------------------
def set_section(name: str, data: Any) -> None:
    """Attach a named section to the run summary (overwrites).  Unlike
    counters/spans this is NOT gated on :func:`enabled` — sections are
    one-shot structured results (the trace-contract report) whose
    producers gate themselves."""
    with _lock:
        _sections[name] = data


def summary() -> Dict[str, Any]:
    """The in-memory run summary as a plain (JSON-serializable) dict.
    Carries this rank's collective flight-recorder state (ring + rolling
    digest) so any cross-rank summary merge doubles as a schedule
    cross-check (see :func:`merged_summary`)."""
    rank, world = _rank_world()
    from . import fleet, flight_recorder
    fr = flight_recorder.snapshot()
    sk = fleet.skew_snapshot()
    ck = fleet.clock()
    with _lock:
        out = {
            "rank": rank,
            "process_count": world,
            "spans": {k: {"count": v[0], "total_s": v[1], "max_s": v[2],
                          "self_s": v[3]}
                      for k, v in _spans.items()},
            "programs": {k: dict(v) for k, v in _programs.items()},
            "counters": dict(_counters),
            "gauges": dict(_gauges),
            "events": dict(_events),
        }
        if fr["count"]:
            out["flight_recorder"] = fr
        if sk is not None:
            out["collective_skew"] = sk
        if ck.get("offset_s") is not None:
            out["clock"] = ck
        out.update(_sections)
        return out


def merged_summary(allgather) -> Dict[str, Any]:
    """Every rank's summary merged into one dict (identical on all
    ranks — ``allgather`` is the host-collective seam, normally
    ``io.distributed.jax_process_allgather``).  ``ranks`` keeps each
    rank's full summary; ``counters``/``events``/``programs`` sum and
    ``spans`` combine across ranks.  The per-rank ``flight_recorder`` sections
    are cross-checked here: a schedule desync lands in
    ``flight_recorder_check`` naming the first diverging site+rank."""
    locals_ = allgather(summary())
    merged: Dict[str, Any] = {
        "process_count": len(locals_),
        "ranks": locals_,
        "spans": {},
        "programs": {},
        "counters": {},
        "events": {},
    }
    for s in locals_:
        for k, v in s.get("counters", {}).items():
            merged["counters"][k] = merged["counters"].get(k, 0) + v
        for k, v in s.get("events", {}).items():
            merged["events"][k] = merged["events"].get(k, 0) + v
        for k, v in s.get("spans", {}).items():
            agg = merged["spans"].setdefault(
                k, {"count": 0, "total_s": 0.0, "max_s": 0.0, "self_s": 0.0})
            agg["count"] += v["count"]
            agg["total_s"] += v["total_s"]
            agg["max_s"] = max(agg["max_s"], v["max_s"])
            agg["self_s"] += v.get("self_s", 0.0)
        for k, v in s.get("programs", {}).items():
            row = merged["programs"].setdefault(k, dict.fromkeys(v, 0))
            for col, n in v.items():
                row[col] += n
    from . import flight_recorder
    check = flight_recorder.cross_check_summaries(locals_)
    if check is not None:
        merged["flight_recorder_check"] = check
    # per-site collective arrival skew lifted fleet-wide: each rank's
    # wait totals side by side, plus the dominant straggler per site
    from . import fleet
    skew = fleet.merge_skew(locals_)
    if skew is not None:
        merged["collective_skew"] = skew
    # per-rank health state, first-class (the ranks already carry their
    # full `health` sections; the lift makes the fleet view one read):
    # `worst` is what a supervisor should act on
    hs = [(s.get("health") or {}).get("state") for s in locals_]
    if any(hs):
        order = ("ready", "warming", "draining", "degraded", "stalled")
        known = [h for h in hs if h in order]
        merged["health"] = {
            "ranks": hs,
            "worst": (max(known, key=order.index) if known else None),
        }
    return merged


def write_summary(path: str, merged: Optional[Dict[str, Any]] = None) -> None:
    """Atomically write a summary (merged or this rank's) as JSON."""
    from ..utils.file_io import atomic_write
    atomic_write(path, json.dumps(merged if merged is not None
                                  else summary(), indent=1))


_init_from_env()
