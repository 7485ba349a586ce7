"""Low-overhead training telemetry (see ``obs/telemetry.py``).

Import seam for the rest of the library::

    from ..obs import span, counter_add, event
    with span("snapshot.write") as s:
        ...
        s["bytes"] = n

The collective flight recorder (``obs/flight_recorder.py``) rides the
same summary plumbing: ``from ..obs import flight_recorder``; the
device-time attribution layer (``obs/profiler.py`` — profiler-backed
capture, trace parser, XLA cost/roofline model) likewise:
``from ..obs import profiler``; the live ops plane (``obs/ops_plane.py``
— scrapeable /metrics + /healthz + /drain) and its health state
machine / stall watchdog / numerics sentinels (``obs/health.py``):
``from ..obs import health, ops_plane``.
"""
from .telemetry import (counter_add, disable, enable, enabled, event,
                        gauge_set, merged_summary, reset, set_clock_offset,
                        set_rank, set_section, set_sink,
                        span, summary, trace_path, write_summary)

__all__ = [
    "enabled", "enable", "disable", "reset", "span", "counter_add",
    "gauge_set", "event", "summary", "merged_summary", "write_summary",
    "trace_path", "set_section", "set_sink",
    "set_clock_offset", "set_rank",
]
