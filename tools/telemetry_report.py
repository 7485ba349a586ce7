#!/usr/bin/env python
"""Render a phase-breakdown table from a telemetry JSONL trace.

Usage::

    python tools/telemetry_report.py /tmp/run.jsonl [more.jsonl ...]
    python tools/telemetry_report.py /tmp/run.jsonl.summary.json

Reads trace files written via ``LGBM_TPU_TRACE=<path>`` or the
``telemetry_output`` config parameter (multi-host runs write one
``<path>.rank<k>`` file per rank — pass them all to merge).  Prints:

* per-span phase breakdown (count, total seconds, self seconds — the
  span's time less its child spans': what lies under none of their
  names —, share of the summed span time at that nesting depth, max
  single duration),
* counters (retry attempts/backoff, snapshot bytes, compile counts...),
* one-shot events (faults fired, early stopping).

The share column uses DEPTH-0 spans as the denominator: nested spans
(e.g. ``gbdt.block`` inside ``gbdt.train`` inside ``engine.train``)
would otherwise double-count wall-clock.  See README "Observability"
for the event schema.

A ``*.summary.json`` argument (one JSON object, not JSONL) is
rendered from the summary side instead — including the
``device_attribution`` section a ``LGBM_TPU_PROFILE`` run attaches
(per-span DEVICE time, host gap, roofline columns), via
``tools/perf_report.py``.
"""
import json
import sys
from collections import defaultdict


def load_records(paths):
    records = []
    for path in paths:
        with open(path) as f:
            for line in f:
                line = line.strip()
                if line:
                    records.append(json.loads(line))
    return records


def health_block(events, counters, state=None, ranks=None,
                 out=sys.stdout):
    """The "Health" section (ISSUE 13): live-plane state, watchdog
    arms/fires, sentinel trips, and every ``health:*`` event — the
    post-hoc rendering of what ``/healthz`` + ``/metrics`` served
    live.  Skipped entirely when the run carried no health signal."""
    h_events = {k: v for k, v in events.items() if k.startswith("health:")}
    h_counters = {k: v for k, v in counters.items()
                  if k.startswith(("watchdog.", "health."))}
    if not (h_events or h_counters or state or ranks):
        return
    print("\n== health ==", file=out)
    if state:
        det = state.get("detail") or {}
        extra = (" (" + ", ".join(f"{k}={v}" for k, v in det.items())
                 + ")") if det else ""
        print(f"  state: {state.get('state', '?')}{extra}", file=out)
    if ranks:
        per = ", ".join(f"rank{r}={s or '?'}"
                        for r, s in enumerate(ranks.get("ranks", [])))
        print(f"  per-rank: {per}    worst: {ranks.get('worst')}",
              file=out)
    arms = int(h_counters.get("watchdog.arms", 0))
    fires = int(h_counters.get("watchdog.fires", 0))
    if arms or fires:
        print(f"  watchdog: {arms} arm(s), {fires} fire(s)", file=out)
    checks = int(h_counters.get("health.sentinel_checks", 0))
    trips = (int(h_counters.get("health.nonfinite", 0))
             + int(h_counters.get("health.loss_spikes", 0)))
    if checks or trips:
        print(f"  sentinels: {checks} check(s), {trips} trip(s)",
              file=out)
    for name in sorted(h_events):
        print(f"  {name:<38s} {h_events[name]:>12d}", file=out)


def report(records, out=sys.stdout):
    # count, total, max, min_depth, self
    spans = defaultdict(lambda: [0, 0.0, 0.0, 0, 0.0])
    counters = {}
    events = defaultdict(int)
    ranks = set()
    for r in records:
        ranks.add(r.get("rank", 0))
        kind = r.get("kind")
        if kind == "span":
            agg = spans[r["name"]]
            agg[0] += 1
            agg[1] += r.get("dur_s", 0.0)
            agg[2] = max(agg[2], r.get("dur_s", 0.0))
            agg[3] = min(agg[3], r.get("depth", 0)) if agg[0] > 1 \
                else r.get("depth", 0)
            # a trace from before spans carried it: all of the span
            agg[4] += r.get("self_s", r.get("dur_s", 0.0))
        elif kind == "counter":
            counters[r["name"]] = r.get("value", 0)
        elif kind == "event":
            events[f'{r.get("family", "event")}:{r["name"]}'] += 1

    wall = sum(v[1] for v in spans.values() if v[3] == 0) or 1.0
    print(f"ranks: {sorted(ranks)}    depth-0 span time: {wall:.3f}s",
          file=out)
    print(f"\n{'phase':<28s} {'count':>7s} {'total_s':>10s} "
          f"{'self_s':>10s} {'share':>7s} {'max_s':>9s}", file=out)
    print("-" * 75, file=out)
    for name, (cnt, total, mx, depth, self_s) in sorted(
            spans.items(), key=lambda kv: -kv[1][1]):
        share = f"{100.0 * total / wall:5.1f}%" if depth == 0 else "     -"
        indent = "  " * depth
        print(f"{indent + name:<28s} {cnt:>7d} {total:>10.3f} "
              f"{self_s:>10.3f} {share:>7s} {mx:>9.3f}", file=out)
    if counters:
        print("\ncounters:", file=out)
        for name in sorted(counters):
            v = counters[name]
            v = f"{v:.3f}" if isinstance(v, float) and v != int(v) \
                else f"{int(v)}"
            print(f"  {name:<40s} {v:>12s}", file=out)
    if events:
        print("\nevents:", file=out)
        for name in sorted(events):
            print(f"  {name:<40s} {events[name]:>12d}", file=out)
    health_block(events, counters, out=out)


def _try_summary(path):
    """-> a summary dict when ``path`` holds ONE JSON object (the
    ``.summary.json`` surface), else None (JSONL traces parse line-wise)."""
    try:
        with open(path) as f:
            data = json.load(f)
        return data if isinstance(data, dict) else None
    except (OSError, ValueError):
        return None


def collective_skew_block(sk, out=sys.stdout):
    """The "collective skew" section (ISSUE 17): per-site arrival-wait
    accounting.  Renders both shapes — a single-rank summary carries
    this rank's wait/xfer totals; a merged summary carries the
    side-by-side per-rank table with the dominant straggler."""
    if not sk:
        return
    print("\n== collective skew ==", file=out)
    for site in sorted(sk):
        st = sk[site]
        if "per_rank_wait_s" in st:     # merged (fleet) shape
            waits = ", ".join(f"r{r}={w:.3f}s" for r, w in
                              enumerate(st.get("per_rank_wait_s", [])))
            line = (f"  {site:<34s} waves={st.get('waves', 0):<5d} "
                    f"wait[{waits}] max={st.get('wait_max_s', 0.0):.3f}s")
            if "straggler_rank" in st:
                line += (f"  straggler: rank {st['straggler_rank']} "
                         f"({st.get('straggler_pct', 0.0):.0f}% of waves)")
            print(line, file=out)
        else:                           # single-rank shape
            print(f"  {site:<34s} waves={st.get('waves', 0):<5d} "
                  f"wait={st.get('wait_total_s', 0.0):.3f}s "
                  f"xfer={st.get('xfer_total_s', 0.0):.3f}s "
                  f"max_wait={st.get('wait_max_s', 0.0):.3f}s "
                  f"straggler_waves={st.get('straggler_waves', 0)}",
                  file=out)


def programs_block(programs, out=sys.stdout, top=12):
    """The compile record: what JAX traced, lowered and compiled, by
    program name, the dearest first; the rest as one line."""
    if not programs:
        return
    cols = ("trace_s", "lower_s", "backend_s")
    rows = sorted(programs.items(),
                  key=lambda kv: -sum(kv[1][c] for c in cols))
    print(f"\n{'program':<28s} {'compiled':>8s} {'trace_s':>9s} "
          f"{'lower_s':>9s} {'backend_s':>10s}", file=out)
    print("-" * 68, file=out)
    rest = {"count": 0, **dict.fromkeys(cols, 0.0)}
    for i, (name, v) in enumerate(rows):
        if i < top:
            print(f"{name:<28s} {v['count']:>8d} {v['trace_s']:>9.3f} "
                  f"{v['lower_s']:>9.3f} {v['backend_s']:>10.3f}", file=out)
        else:
            for c in rest:
                rest[c] += v[c]
    if len(rows) > top:
        print(f"{f'({len(rows) - top} more)':<28s} {rest['count']:>8d} "
              f"{rest['trace_s']:>9.3f} {rest['lower_s']:>9.3f} "
              f"{rest['backend_s']:>10.3f}", file=out)


def report_summary(s, out=sys.stdout):
    """Host-side span table from a summary dict, then the device-time
    attribution section when the run was profiled."""
    spans = s.get("spans", {})
    total = sum(v.get("total_s", 0.0) for v in spans.values()) or 1.0
    print(f"summary: rank {s.get('rank', '?')} / "
          f"{s.get('process_count', '?')} process(es)", file=out)
    print(f"\n{'span':<28s} {'count':>7s} {'total_s':>10s} {'self_s':>10s} "
          f"{'max_s':>9s}", file=out)
    print("-" * 69, file=out)
    for name, v in sorted(spans.items(), key=lambda kv: -kv[1]["total_s"]):
        print(f"{name:<28s} {v['count']:>7d} {v['total_s']:>10.3f} "
              f"{v.get('self_s', v['total_s']):>10.3f} {v['max_s']:>9.3f}",
              file=out)
    programs_block(s.get("programs"), out=out)
    # Health section: a single-rank summary carries its own `health`
    # state; a merged multi-rank summary carries the per-rank lift
    # (telemetry.merged_summary) — both render here
    hstate = s.get("health") if "state" in (s.get("health") or {}) else None
    hranks = s.get("health") if "ranks" in (s.get("health") or {}) else None
    health_block(s.get("events", {}), s.get("counters", {}),
                 state=hstate, ranks=hranks, out=out)
    collective_skew_block(s.get("collective_skew"), out=out)
    da = s.get("device_attribution")
    if da:
        print("\n== device attribution (LGBM_TPU_PROFILE capture) ==",
              file=out)
        try:
            from tools.perf_report import render
        except ImportError:     # invoked as `python tools/telemetry_report.py`
            from perf_report import render
        render(da, out=out)


def main(argv):
    if not argv:
        print(__doc__)
        return 1
    summaries = [p for p in argv if _try_summary(p) is not None]
    traces = [p for p in argv if p not in summaries]
    for p in summaries:
        report_summary(_try_summary(p))
    if traces:
        report(load_records(traces))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
