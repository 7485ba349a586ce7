"""Device-time attribution: profiler-backed span accounting.

The host-side telemetry spans (``obs/telemetry.py``) time DISPATCH,
not execution: a span around an async JAX dispatch closes when the
host returns, while XLA is still running.  Every open perf question on
the ROADMAP — per-iteration host latency on the mesh path, the
ranking regime, the never-captured 255-bin leg — needs the other half:
where the DEVICE time goes, per phase.  This module is that layer.

* **Capture** — under ``LGBM_TPU_PROFILE=<dir>`` every training run
  profiles itself: once the first (warmup) window is done,
  ``jax.profiler.start_trace`` begins a WINDOWED capture (the next
  ``LGBM_TPU_PROFILE_WINDOWS`` windows of ``LGBM_TPU_PROFILE_ITERS``
  iterations each, so the trace stays bounded inside bench runs),
  then stops, parses, and drops the result into the telemetry summary
  as the ``device_attribution`` section.  Every telemetry span enters
  a ``jax.profiler.TraceAnnotation`` of its own name
  (``obs/telemetry.py``), so XLA ops attribute to the existing span
  tree without a second instrumentation pass.  Works on the CPU
  backend — tier-1 gates the whole pipeline without TPU hardware.

* **Parse** — :func:`parse_capture` reads the profiler's chrome-trace
  JSON (``plugins/profile/<ts>/*.trace.json.gz``; stdlib only) and
  :func:`attribute` reduces it to the per-span table: ``device_s`` per
  span (each HLO-op event joins the deepest annotation covering its
  midpoint, falling back to the latest annotation started before it —
  async dispatch runs AFTER its span closes), ``host_gap_s`` (device
  idle inside the training windows: the ROADMAP item-1 metric),
  collective wall time (op-name families: all-reduce / all-gather /
  reduce-scatter / all-to-all / collective-permute — the sites the
  flight recorder names), and per-program (``hlo_module``) totals.

* **Cost model** — :func:`record_program_cost` snapshots
  ``Compiled.cost_analysis()`` (FLOPs, bytes accessed) for each jitted
  program at block-compile time (gated on the same env: an extra
  lower+compile is acceptable in an explicit profiling run, never in a
  timed one); :func:`finalize` joins those with the measured
  per-program device time and the ``obs/chip_specs.py`` peak table
  into roofline columns — %-of-peak FLOPs/BW, arithmetic intensity,
  and a compute/memory/host ``bound`` verdict per program.

Capture is best-effort by construction: a profiler that fails to
start, a trace that fails to parse, disk full — all degrade to a
``device_attribution`` section carrying an ``error`` field.  Training
must never die for observability's sake.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import time
from typing import Any, Dict, List, Optional, Tuple

from . import telemetry

__all__ = [
    "profile_dir", "cost_model_enabled", "maybe_profile", "capture",
    "step", "record_program_cost", "program_costs", "reset",
    "parse_capture", "attribute", "finalize_report",
    "ATTRIBUTION_SECTION",
]

PROFILE_ENV = "LGBM_TPU_PROFILE"
ATTRIBUTION_SECTION = "device_attribution"

# span-name prefixes the parser recognizes as OUR annotations (the
# telemetry span tree + the step markers) — everything else on the
# host timeline is runtime internals ($-prefixed python frames,
# PjitFunction, executor plumbing)
SPAN_PREFIXES = ("engine.", "gbdt.", "tree.", "serve.", "io.", "mesh.",
                 "collective.", "obj.", "snapshot.", "bench.", "profile.")
# training-window spans: their wall clock minus in-window device busy
# time is the host gap (idle device between consecutive dispatches)
WINDOW_SPANS = ("gbdt.block", "gbdt.block_compile", "gbdt.iteration")
COLLECTIVE_OPS = ("all-reduce", "all-gather", "reduce-scatter",
                  "all-to-all", "collective-permute", "psum")


def profile_dir() -> str:
    return os.environ.get(PROFILE_ENV, "")


def profile_windows() -> int:
    """Captured windows after warmup (bounded trace size)."""
    return max(1, int(os.environ.get("LGBM_TPU_PROFILE_WINDOWS", 2)))


def profile_window_iters() -> int:
    """Iterations per training window while a profile session is live
    (the session clamps the train loop's window so 'first N post-warmup
    iterations' is well defined even when the run would otherwise fuse
    everything into one block)."""
    return max(1, int(os.environ.get("LGBM_TPU_PROFILE_ITERS", 2)))


def cost_model_enabled() -> bool:
    """The static XLA cost model records when profiling is on, or
    standalone under ``LGBM_TPU_COST_MODEL=1`` (it costs one extra
    lower+compile per program — never free, so never default-on)."""
    return bool(profile_dir()) \
        or os.environ.get("LGBM_TPU_COST_MODEL", "") == "1"


# ---------------------------------------------------------------------------
# capture state (one live capture per process — jax.profiler is global)
# ---------------------------------------------------------------------------
_active_dir: Optional[str] = None
_program_costs: Dict[str, Dict[str, Any]] = {}


class _NoopCtx:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NOOP_CTX = _NoopCtx()


def step(name: str, num: int):
    """A ``jax.profiler.StepTraceAnnotation`` while a capture is live,
    else a shared no-op — per-batch/iteration step markers for the
    serving harness and the capture CLI."""
    if _active_dir is None:
        return _NOOP_CTX
    import jax
    return jax.profiler.StepTraceAnnotation(name, step_num=num)


def _start_capture(out_dir: str) -> bool:
    """Start the global jax profiler into ``out_dir``.  Returns False
    (and logs once) when the profiler cannot start — the caller
    degrades to no capture."""
    global _active_dir
    if _active_dir is not None:
        return False                    # one capture at a time
    # a capture is only useful with live spans to annotate: enabling
    # telemetry here (in-memory summary only — no trace file unless one
    # was separately requested) makes LGBM_TPU_PROFILE self-sufficient
    telemetry.enable()
    try:
        import jax
        os.makedirs(out_dir, exist_ok=True)
        jax.profiler.start_trace(out_dir)
    # tpulint: disable=TPL006 -- capture is best-effort; failure is logged
    except Exception as exc:            # noqa: BLE001 - degrade, never die
        from ..utils.log import log_once
        log_once("profiler_start_failed",
                 f"device-time capture failed to start ({exc}); "
                 f"continuing unprofiled", level="warning")
        return False
    _active_dir = out_dir
    return True


def _stop_capture(sync=None) -> Optional[str]:
    """Stop the live capture (after ``sync()`` blocks on in-flight
    work, so the captured windows' device ops land inside the trace).
    Returns the capture dir, or None when nothing was live."""
    global _active_dir
    out, _active_dir = _active_dir, None
    if out is None:
        return None
    if sync is not None:
        try:
            sync()
        # tpulint: disable=TPL006 -- sync is best-effort capture hygiene
        except Exception:               # noqa: BLE001 - trace still stops
            pass
    try:
        import jax
        jax.profiler.stop_trace()
    # tpulint: disable=TPL006 -- capture is best-effort; failure is logged
    except Exception as exc:            # noqa: BLE001 - degrade, never die
        from ..utils.log import log_warning
        log_warning(f"device-time capture failed to stop cleanly: {exc}")
        return None
    return out


def reset() -> None:
    """Forget capture/cost state (tests); stops a leaked live capture."""
    global _program_costs
    if _active_dir is not None:
        _stop_capture()
    _program_costs = {}


class capture:
    """``with capture(out_dir, sync=...) as c:`` — plain bounded
    capture for tools (``tools/profile_capture.py``): annotated spans
    inside the block land in the trace; on exit the capture is parsed
    and ``c.report`` holds the attribution dict (also written to the
    telemetry summary section)."""

    def __init__(self, out_dir: str, sync=None, section: str
                 = ATTRIBUTION_SECTION):
        self.out_dir = out_dir
        self.sync = sync
        self.section = section
        self.report: Optional[Dict[str, Any]] = None

    def __enter__(self) -> "capture":
        self._started = _start_capture(self.out_dir)
        return self

    def __exit__(self, *exc) -> bool:
        if self._started:
            path = _stop_capture(self.sync)
            self.report = finalize_report(path or self.out_dir)
            telemetry.set_section(self.section, self.report)
        return False


# ---------------------------------------------------------------------------
# windowed training session
# ---------------------------------------------------------------------------
class _ProfileSession:
    """Windowed capture driven by the training loop: window 0 is
    warmup (block compiles + first-touch allocations), then
    ``profile_windows()`` captured windows, then stop + parse + attach
    the section — mid-train, so a long run carries a bounded trace."""

    def __init__(self, kind: str, out_dir: str, sync=None):
        self.kind = kind
        self.out_dir = out_dir
        self.sync = sync
        self.state = "warmup"           # -> capturing -> done
        self.windows_left = profile_windows()
        self.chunk = profile_window_iters()
        self.report: Optional[Dict[str, Any]] = None
        self._t0 = time.perf_counter()

    def clamp_window(self, requested: int) -> int:
        """Bound the train loop's next window while the session is
        live, so warmup/capture boundaries fall every ``chunk``
        iterations (a fused 500-iteration block would otherwise be one
        giant window and the capture would never start)."""
        if self.state == "done":
            return requested
        return max(1, min(requested, self.chunk))

    def window(self, it: int = -1) -> bool:
        """One training window finished.  Advances warmup -> capture
        -> done.  Returns True when this boundary did heavy profiler
        work (trace start / stop+parse) — the caller excludes that
        from its own host-latency accounting: observer overhead is not
        training host gap."""
        if self.state == "warmup":
            self.state = "capturing"
            if not _start_capture(self.out_dir):
                self.state = "done"
            return True
        if self.state == "capturing":
            self.windows_left -= 1
            if self.windows_left <= 0:
                self._finish(it)
                return True
        return False

    def _finish(self, it: int = -1) -> None:
        if self.state != "capturing":
            return
        self.state = "done"
        path = _stop_capture(self.sync)
        self.report = finalize_report(path or self.out_dir)
        self.report["kind"] = self.kind
        self.report["windows"] = profile_windows()
        self.report["window_iters"] = self.chunk
        if it >= 0:
            self.report["captured_through_iteration"] = int(it)
        telemetry.set_section(ATTRIBUTION_SECTION, self.report)

    def close(self) -> None:
        """End-of-train: stop a still-running capture (short runs end
        before the window budget is spent)."""
        self._finish()


class maybe_profile:
    """``with maybe_profile("gbdt", sync=...) as prof:`` — a live
    :class:`_ProfileSession` when ``LGBM_TPU_PROFILE`` names a capture
    directory, else None at ~zero cost (one env read per train)."""

    def __init__(self, kind: str, sync=None):
        self.kind = kind
        self.sync = sync
        self.session: Optional[_ProfileSession] = None

    def __enter__(self) -> Optional[_ProfileSession]:
        out = profile_dir()
        if out:
            self.session = _ProfileSession(self.kind, out, sync=self.sync)
        return self.session

    def __exit__(self, *exc) -> bool:
        if self.session is not None:
            self.session.close()
        return False


# ---------------------------------------------------------------------------
# static cost model
# ---------------------------------------------------------------------------
def _normalize_cost(ca) -> Dict[str, Optional[float]]:
    """``cost_analysis()`` returns a dict on new jax, ``[dict]`` on
    older; keys are xla's space-separated names."""
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    if not isinstance(ca, dict):
        return {"flops": None, "bytes_accessed": None}
    flops = ca.get("flops")
    by = ca.get("bytes accessed", ca.get("bytes_accessed"))
    return {"flops": float(flops) if flops is not None else None,
            "bytes_accessed": float(by) if by is not None else None}


def record_program_cost(name: str, fn, args: Tuple = (),
                        module_hint: Optional[str] = None,
                        **attrs) -> Optional[Dict[str, Any]]:
    """Record FLOPs / bytes-accessed for one jitted program under
    ``name``.  ``fn`` is either an AOT ``Compiled`` (cost_analysis is
    free) or a ``jax.jit`` wrapper (one extra lower+compile — which is
    why this is gated on :func:`cost_model_enabled`).  The entry lands
    in the telemetry summary's ``xla_cost`` section immediately, so a
    killed run still carries every program compiled so far."""
    if not cost_model_enabled():
        return None
    try:
        if hasattr(fn, "cost_analysis"):
            ca = fn.cost_analysis()
        else:
            ca = fn.lower(*args).compile().cost_analysis()
    # tpulint: disable=TPL006 -- cost model is best-effort; logged once
    except Exception as exc:            # noqa: BLE001 - degrade, never die
        from ..utils.log import log_once
        log_once(f"cost_analysis_failed:{name}",
                 f"cost_analysis for {name} failed ({exc})",
                 level="warning")
        return None
    entry = _normalize_cost(ca)
    if module_hint is None:
        base = getattr(fn, "__name__", None)
        module_hint = f"jit_{base}" if base else None
    entry["hlo_module"] = module_hint
    entry.update(attrs)
    _program_costs[name] = entry
    telemetry.set_section("xla_cost", dict(_program_costs))
    return entry


def program_costs() -> Dict[str, Dict[str, Any]]:
    return dict(_program_costs)


# ---------------------------------------------------------------------------
# trace parsing (chrome trace JSON, stdlib only)
# ---------------------------------------------------------------------------
def find_trace_file(path: str) -> Optional[str]:
    """Resolve a capture root / session dir / trace file to the newest
    ``*.trace.json(.gz)`` (the chrome-trace sidecar the profiler
    writes; ``perfetto_trace.json.gz`` has the same events — either
    parses)."""
    if os.path.isfile(path):
        return path
    pats = (os.path.join(path, "plugins", "profile", "*",
                         "*.trace.json.gz"),
            os.path.join(path, "*.trace.json.gz"),
            os.path.join(path, "plugins", "profile", "*",
                         "perfetto_trace.json.gz"))
    for pat in pats:
        hits = sorted(glob.glob(pat))
        if hits:
            return hits[-1]             # newest session sorts last
    return None


def parse_capture(path: str) -> Dict[str, Any]:
    """Parse one capture into ``{"annotations": [...], "ops": [...],
    "path": file}``.  Annotations are OUR span/step events (dotted
    names in :data:`SPAN_PREFIXES`) on any thread; ops are XLA
    executions — events carrying ``hlo_op``/``hlo_module`` args (CPU
    executor threads), or any timed event on a ``/device:*`` process
    (TPU device lines).  Times are seconds relative to the trace."""
    f = find_trace_file(path)
    if f is None:
        raise FileNotFoundError(f"no trace.json(.gz) under {path!r}")
    opener = gzip.open if f.endswith(".gz") else open
    with opener(f, "rt", encoding="utf-8") as fh:
        data = json.load(fh)
    events = data.get("traceEvents", data if isinstance(data, list) else [])
    procs: Dict[Any, str] = {}
    annos: List[Dict[str, Any]] = []
    ops: List[Dict[str, Any]] = []
    for ev in events:
        if not ev:
            continue
        if ev.get("ph") == "M":
            if ev.get("name") == "process_name":
                procs[ev.get("pid")] = ev.get("args", {}).get("name", "")
            continue
        if ev.get("ph") != "X":
            continue
        name = ev.get("name", "")
        args = ev.get("args") or {}
        ts = float(ev.get("ts", 0.0)) / 1e6
        dur = float(ev.get("dur", 0.0)) / 1e6
        if "hlo_op" in args or "hlo_module" in args:
            ops.append({"name": name, "ts": ts, "dur": dur,
                        "module": args.get("hlo_module", "")})
        elif str(procs.get(ev.get("pid"), "")).startswith("/device:"):
            ops.append({"name": name, "ts": ts, "dur": dur,
                        "module": args.get("hlo_module", "")})
        elif name.startswith(SPAN_PREFIXES):
            annos.append({"name": name, "ts": ts, "dur": dur})
    annos.sort(key=lambda a: a["ts"])
    ops.sort(key=lambda o: o["ts"])
    return {"annotations": annos, "ops": ops, "path": f}


def _interval_union(iv: List[Tuple[float, float]]) -> float:
    total, end = 0.0, -1.0
    for s, e in sorted(iv):
        if s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def _is_collective(op_name: str) -> bool:
    n = op_name.lower()
    return any(n.startswith(c) or f"/{c}" in n for c in COLLECTIVE_OPS)


def attribute(parsed: Dict[str, Any]) -> Dict[str, Any]:
    """Reduce a parsed capture to the per-span device-time table.

    Each op joins the DEEPEST annotation covering its midpoint
    (deepest = latest-starting cover: our spans nest); ops that start
    after their span closed (async dispatch) fall back to the latest
    annotation STARTED at-or-before the op's start — in a dispatch
    loop that is exactly the span that enqueued them."""
    annos, ops = parsed["annotations"], parsed["ops"]
    spans: Dict[str, Dict[str, Any]] = {}
    programs: Dict[str, float] = {}
    device_total = attributed = collective_s = 0.0
    for op in ops:
        device_total += op["dur"]
        mod = op["module"] or "<unnamed>"
        programs[mod] = programs.get(mod, 0.0) + op["dur"]
        if _is_collective(op["name"]):
            collective_s += op["dur"]
        mid = op["ts"] + op["dur"] / 2.0
        owner = None
        for a in annos:                 # sorted by ts: last hit wins
            if a["ts"] > mid:
                break
            if a["ts"] + a["dur"] >= mid:
                owner = a
        if owner is None:
            for a in annos:
                if a["ts"] > op["ts"]:
                    break
                owner = a               # latest started at-or-before
        if owner is None:
            continue
        attributed += op["dur"]
        agg = spans.setdefault(owner["name"],
                               {"device_s": 0.0, "ops": 0})
        agg["device_s"] += op["dur"]
        agg["ops"] += 1

    # host gap: device idle inside the training windows (dispatch
    # return -> next dispatch's ops, the ROADMAP item-1 latency)
    windows = [(a["ts"], a["ts"] + a["dur"]) for a in annos
               if a["name"] in WINDOW_SPANS]
    window_wall = sum(e - s for s, e in windows)
    busy_in_windows = _interval_union(
        [(max(o["ts"], s), min(o["ts"] + o["dur"], e))
         for o in ops for s, e in windows
         if o["ts"] < e and o["ts"] + o["dur"] > s])
    # capture-wide accounting: wall from first annotation/op to the
    # last op end, minus total device busy
    points = ([a["ts"] for a in annos] + [o["ts"] for o in ops])
    ends = ([a["ts"] + a["dur"] for a in annos]
            + [o["ts"] + o["dur"] for o in ops])
    capture_wall = (max(ends) - min(points)) if points else 0.0
    device_busy = _interval_union([(o["ts"], o["ts"] + o["dur"])
                                   for o in ops])
    top = sorted(programs.items(), key=lambda kv: -kv[1])[:3]
    return {
        "source": parsed.get("path"),
        "device_time_s": round(device_total, 6),
        "attributed_s": round(attributed, 6),
        "coverage": round(attributed / device_total, 4)
        if device_total else None,
        "collective_s": round(collective_s, 6),
        "collective_frac": round(collective_s / device_total, 4)
        if device_total else None,
        "capture_wall_s": round(capture_wall, 6),
        "device_busy_s": round(device_busy, 6),
        "host_gap_s": round(max(0.0, window_wall - busy_in_windows), 6),
        "window_wall_s": round(window_wall, 6),
        "spans": {k: {"device_s": round(v["device_s"], 6),
                      "ops": v["ops"]}
                  for k, v in sorted(spans.items(),
                                     key=lambda kv: -kv[1]["device_s"])},
        "programs": {k: round(v, 6) for k, v in
                     sorted(programs.items(), key=lambda kv: -kv[1])},
        "top_programs": [[k, round(v, 6)] for k, v in top],
        "annotations": len(annos),
        "ops": len(ops),
    }


def finalize_report(path: str) -> Dict[str, Any]:
    """Parse + attribute a capture and join the recorded program costs
    into roofline columns.  Never raises: failures land as an
    ``error`` field so the summary section always exists."""
    try:
        report = attribute(parse_capture(path))
    # tpulint: disable=TPL006 -- attribution is best-effort; error recorded
    except Exception as exc:            # noqa: BLE001 - degrade, never die
        return {"error": f"{type(exc).__name__}: {exc}", "source": path}
    from .chip_specs import peaks_for, roofline
    peaks = peaks_for()
    rows = []
    measured = report["programs"]
    for name, cost in _program_costs.items():
        hint = cost.get("hlo_module") or ""
        dev_s = None
        for mod, s in measured.items():
            if hint and (mod == hint or mod.startswith(hint)):
                dev_s = s
                break
        row = {"program": name, "hlo_module": hint or None,
               "device_s": dev_s}
        row.update(roofline(cost.get("flops"), cost.get("bytes_accessed"),
                            dev_s, peaks))
        rows.append(row)
    report["cost_model"] = {
        "device_kind": peaks.get("kind"),
        "peaks": {k: peaks.get(k) for k in
                  ("flops_per_s", "hbm_bytes_per_s", "source",
                   "sentinel") if peaks.get(k) is not None},
        "programs": rows,
    }
    return report
