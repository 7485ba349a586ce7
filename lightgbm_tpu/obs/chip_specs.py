"""Per-device-kind peak tables for the roofline columns.

The device-time attribution layer (``obs/profiler.py``) turns
``Compiled.cost_analysis()`` FLOPs/bytes plus measured per-program
device time into %-of-peak and arithmetic-intensity columns.  That
needs ONE small authoritative table of nameplate peaks per device
kind — kept here, jax-free except for the kind probe, so the trace
parser and ``tools/perf_report.py`` can import it without touching a
backend.

Numbers are NAMEPLATE (vendor-published) peaks, not measured: what a
chained matmul reaches of them is not measured on a local chip.
Roofline percentages computed against nameplate are conservative; a
program reading ">90% of peak" genuinely has no headroom.

The ``cpu`` entry is an explicit SENTINEL: tier-1 runs the whole
attribution pipeline on the CPU backend, where "% of peak" against a
per-box-variable peak would be meaningless — the sentinel keeps the
column arithmetic exercised (and flagged ``sentinel: true`` in every
report) without pretending to measure a CPU roofline.
"""
from __future__ import annotations

import sys
from typing import Any, Dict, Optional

__all__ = ["CHIP_PEAKS", "device_kind", "peaks_for", "roofline"]

# kind -> {flops_per_s (bf16 for TPUs), hbm_bytes_per_s, source}
CHIP_PEAKS: Dict[str, Dict[str, Any]] = {
    "tpu-v5e": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                "source": "v5e nameplate: 197 bf16 TFLOPs, 819 GB/s HBM"},
    "tpu-v5p": {"flops_per_s": 459e12, "hbm_bytes_per_s": 2765e9,
                "source": "v5p nameplate: 459 bf16 TFLOPs, 2765 GB/s HBM"},
    "tpu-v4": {"flops_per_s": 275e12, "hbm_bytes_per_s": 1228e9,
               "source": "v4 nameplate: 275 bf16 TFLOPs, 1228 GB/s HBM"},
    # sentinel, not a measurement: keeps the roofline arithmetic (and
    # its tier-1 gates) runnable on the CPU backend
    "cpu": {"flops_per_s": 1e11, "hbm_bytes_per_s": 5e10,
            "source": "CPU SENTINEL (tier-1 mechanics only)",
            "sentinel": True},
}


def _normalize(kind: str) -> Optional[str]:
    k = (kind or "").lower()
    if "v5e" in k or "v5 lite" in k or "v5lite" in k:
        return "tpu-v5e"
    if "v5p" in k or ("v5" in k and "lite" not in k):
        return "tpu-v5p"
    if "v4" in k:
        return "tpu-v4"
    if "cpu" in k or "host" in k:
        return "cpu"
    return None


def device_kind() -> str:
    """The current jax backend's device kind string (best effort; never
    initializes jax when it is not already imported)."""
    jx = sys.modules.get("jax")
    if jx is None:
        return "unknown"
    try:
        d = jx.devices()[0]
        return str(getattr(d, "device_kind", None) or d.platform)
    # tpulint: disable=TPL006 -- best-effort probe; "unknown" IS the answer
    except Exception:                   # noqa: BLE001 - probe is best-effort
        return "unknown"


def peaks_for(kind: Optional[str] = None) -> Dict[str, Any]:
    """Peak table entry for ``kind`` (default: the current device).
    Unknown kinds return an explicit no-peaks entry — roofline columns
    then carry ``null`` percentages instead of a made-up peak."""
    raw = kind if kind is not None else device_kind()
    key = _normalize(raw)
    if key is None:
        return {"kind": raw, "flops_per_s": None, "hbm_bytes_per_s": None,
                "source": f"no peak table entry for {raw!r}"}
    out = dict(CHIP_PEAKS[key])
    out["kind"] = raw
    out["key"] = key
    return out


def roofline(flops: Optional[float], bytes_accessed: Optional[float],
             device_time_s: Optional[float],
             peaks: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """Roofline columns for one program.

    Static half (needs only the cost model): arithmetic intensity
    (FLOPs/byte) and the device's ridge point — ``ai < ridge`` means
    the program CANNOT be compute-bound on this chip no matter how
    well it runs.  Measured half (needs attributed device time):
    achieved FLOPs/s and bytes/s as a fraction of peak, and a
    ``bound`` verdict — ``compute`` / ``memory`` when the dominant
    fraction is meaningful, ``host`` when both are tiny (the device is
    starved: dispatch latency, not the kernel, is the bottleneck —
    exactly the ROADMAP item-1 signature)."""
    p = peaks if peaks is not None else peaks_for()
    pf, pb = p.get("flops_per_s"), p.get("hbm_bytes_per_s")
    out: Dict[str, Any] = {
        "flops": flops, "bytes_accessed": bytes_accessed,
        "arith_intensity": (flops / bytes_accessed
                            if flops and bytes_accessed else None),
        "ridge_flops_per_byte": (pf / pb if pf and pb else None),
        "pct_peak_flops": None, "pct_peak_bw": None, "bound": None,
    }
    if device_time_s and device_time_s > 0:
        if flops and pf:
            out["pct_peak_flops"] = round(
                100.0 * flops / device_time_s / pf, 3)
        if bytes_accessed and pb:
            out["pct_peak_bw"] = round(
                100.0 * bytes_accessed / device_time_s / pb, 3)
        cf = out["pct_peak_flops"] or 0.0
        cb = out["pct_peak_bw"] or 0.0
        if max(cf, cb) < 5.0:
            out["bound"] = "host"
        else:
            out["bound"] = "compute" if cf >= cb else "memory"
    elif out["arith_intensity"] is not None \
            and out["ridge_flops_per_byte"] is not None:
        # static-only verdict: which roof the program sits under
        out["bound"] = ("compute" if out["arith_intensity"]
                        >= out["ridge_flops_per_byte"] else "memory")
    return out
