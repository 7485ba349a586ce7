"""GPU north-star arithmetic (VERDICT r4 #3) — every letter a number.

BASELINE.md's primary target: beat the reference's OpenCL GPU learner on
HIGGS wall-clock on a single v5e-8.  The reference never states the GPU
learner's HIGGS wall-clock in text (the chart is an image,
`docs/GPU-Performance.rst:164-166`); the only *numeric* speedup in its
docs is "over three times speedup" (`docs/GPU-Tutorial.rst:162`, Higgs on
a half-M60) and the qualitative bound "a *budget* GPU can still compete
and be faster than a 28-core Haswell server"
(`docs/GPU-Performance.rst:172`).  We adopt the AGGRESSIVE reading as the
target: **GPU target = 3.0x the 238.505 s / 22.0M row-iters/s CPU
baseline**, i.e. 66.1M row-iters/s — even though the tutorial's own CPU
was a 6-vCPU VM (so 3x that box is likely < 1x the 28-core box, making
3x a deliberately hard target).

This tool records, on the real chip:
  * measured dense MXU peak (int8 + bf16 matmul microbench),
  * per-wave histogram-kernel time at bench shapes -> MXU utilization,
  * warm end-to-end s/iteration at 1M rows (and 10.5M with FULL=1),
  * all-reduce bytes per tree for the 8-way data-parallel HIGGS config
    (HLO-measured on the virtual CPU mesh; byte volume is row-count
    independent: histograms are [A, F, B, 3]),
and derives: single-chip multiple Y, needed 8-chip scaling Z = X/Y, and
the projected 8-chip multiple from measured per-chip compute vs ICI
all-reduce time (worst case, no overlap).

Timing uses a device->host scalar fetch as the barrier (``bench._sync``;
what one run on a local chip showed of it against ``block_until_ready``
is in PERF.md section 5, PR 21).

Run on TPU:  python tools/north_star.py        (writes tests/data/north_star.json)
             FULL=1 python tools/north_star.py (adds the 10.5M-row leg)
"""
import json
import os
import re
import subprocess
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

ARTIFACT = os.path.join(ROOT, "tests", "data", "north_star.json")

CPU_BASELINE_ROW_ITERS = 10.5e6 * 500 / 238.505     # 22.0M (Experiments.rst)
GPU_TARGET_MULTIPLE = 3.0                           # GPU-Tutorial.rst:162
# public v5e spec: 1600 Gbps interchip interconnect per chip; a ring
# all-reduce of S bytes on 8 chips moves ~2*S*(7/8) per chip -> we use
# an effective 100 GB/s unidirectional aggregate
ICI_EFFECTIVE_GBPS = 100.0


from bench import _sync                           # noqa: E402  (the
# bench's device barrier)


def measured_peak():
    """Dense matmul microbench: the chip's achievable MAC rates.

    The K matmuls are DEPENDENCY-CHAINED inside one jitted fori_loop
    (``a <- cast(a @ w)``) so one dispatch covers the whole chain —
    per-dispatch host latency would otherwise be part of the
    measurement."""
    import jax
    import jax.numpy as jnp
    out = {}
    m = 8192
    for dtype, acc, name in ((jnp.int8, jnp.int32, "int8"),
                             (jnp.bfloat16, jnp.float32, "bf16")):
        a0 = jnp.ones((m, m), dtype)
        w = jnp.eye(m, dtype=dtype)

        def run(K):
            @jax.jit
            def chain(a, w):
                def body(s, _):
                    y = jax.lax.dot_general(
                        s, w, (((1,), (0,)), ((), ())),
                        preferred_element_type=acc)
                    # REAL dependency chain: the next step consumes the
                    # full product (w = identity keeps values bounded),
                    # so the dot cannot be hoisted as loop-invariant
                    return jnp.clip(y, -127, 127).astype(s.dtype), None
                s, _ = jax.lax.scan(body, a, None, length=K)
                return s
            _sync(chain(a0, w))
            t0 = time.time()
            _sync(chain(a0, w))
            return time.time() - t0

        # single-dispatch timing carries a fixed host round-trip: the
        # (K2-K1) slope cancels it exactly
        k1, k2 = 8, 40
        dt = (run(k2) - run(k1)) / (k2 - k1)
        out[f"peak_{name}_tmacs"] = round(m * m * m / dt / 1e12, 1)
    return out


def wave_times(peak_tmacs, f=28, max_bin=63):
    """Histogram-kernel cost per wave by active-slot count, measured as
    the SLOPE between 1M and 4M rows (standalone dispatches carry a
    fixed host latency each; the slope cancels every fixed cost)."""
    import jax.numpy as jnp
    from lightgbm_tpu.ops import pallas_histogram as ph
    B = ph.bin_stride(max_bin)
    sizes = (1_000_000, 4_000_000)
    ms_at = {}
    for n in sizes:
        rng = np.random.RandomState(0)
        bins = rng.randint(0, max_bin + 1, size=(n, f)).astype(np.uint8)
        bt = jnp.asarray(ph.transpose_bins_host(bins))
        del bins
        g = jnp.asarray(rng.normal(size=n).astype(np.float32))
        h = jnp.abs(jnp.asarray(rng.normal(size=n).astype(np.float32)))
        row_leaf = jnp.asarray(
            rng.randint(0, 255, size=bt.shape[1]).astype(np.int32))
        vals, scales = ph.pack_values_q(g, h, "int8h")
        for a in (1, 8, 32, 64, 128):
            act = jnp.arange(a, dtype=jnp.int32)
            out = ph.hist_active_pallas(bt, vals, row_leaf, act, scales,
                                        num_features=f, max_bins=max_bin,
                                        mode="int8h")
            _sync(out)
            reps = 10
            t0 = time.time()
            for _ in range(reps):
                out = ph.hist_active_pallas(bt, vals, row_leaf, act,
                                            scales, num_features=f,
                                            max_bins=max_bin, mode="int8h")
            _sync(out)
            ms_at[(a, n)] = (time.time() - t0) / reps * 1e3
        del bt, g, h, vals, row_leaf
        import gc
        gc.collect()
    rows = []
    for a in (1, 8, 32, 64, 128):
        slope_ns = ((ms_at[(a, sizes[1])] - ms_at[(a, sizes[0])]) * 1e6
                    / (sizes[1] - sizes[0]))
        cols = ph._col_layout(a, "int8h")[2]
        macs_row = f * B * cols
        tmacs = macs_row / max(slope_ns, 1e-9) / 1e3
        rows.append({"active": a, "ns_per_row": round(slope_ns, 2),
                     "dispatch_ms_1m": round(ms_at[(a, sizes[0])], 2),
                     "mxu_util_vs_measured_peak": round(
                         tmacs / peak_tmacs, 3)})
    return rows


def iter_time(n, iters=32, leaves=255, max_bin=63):
    """Warm end-to-end training s/iteration at the bench config."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu.basic import Booster
    rng = np.random.RandomState(0)
    X = rng.normal(size=(n, 28)).astype(np.float32)
    y = (X[:, 0] * 2 + X[:, 1] - X[:, 2]
         + rng.normal(size=n) > 0).astype(np.float32)
    ds = lgb.Dataset(X, label=y, params={"max_bin": max_bin})
    ds.construct()
    del X
    params = {"objective": "binary", "num_leaves": leaves,
              "max_bin": max_bin, "learning_rate": 0.1,
              "min_data_in_leaf": 20, "verbose": -1}
    bst = Booster(params=params, train_set=ds)
    g = bst._gbdt
    bst.update()
    g.train_block(3 * iters)
    _sync(g.scores)

    def run(k):
        t0 = time.time()
        g.train_block(k)
        _sync(g.scores)
        return time.time() - t0

    # slope between two window lengths cancels the per-call host
    # round-trip
    dt = (run(3 * iters) - run(iters)) / (2 * iters)
    del bst, ds, g
    import gc
    gc.collect()
    return dt


_DT = {"f64": 8, "f32": 4, "bf16": 2, "s32": 4, "u32": 4, "s8": 1,
       "u8": 1, "pred": 1, "s64": 8, "u64": 8, "f16": 2}


def _collective_bytes(txt):
    total = 0
    for m in re.finditer(
            r"=\s*(\([^)]*\)|\S+)\s+"
            r"(?:all-reduce|all-gather|reduce-scatter)(?:-start)?\(",
            txt):
        shapes = re.findall(r"(f64|f32|bf16|f16|s64|u64|s32|u32|s8|u8|pred)"
                            r"\[([\d,]*)\]", m.group(1))
        for dt, dims in shapes:
            elems = 1
            for d in dims.split(","):
                if d:
                    elems *= int(d)
            total += elems * _DT[dt]
    return total


def collective_bytes_per_tree():
    """All-reduce bytes for one 255-leaf data-parallel tree at the HIGGS
    bin/feature config, measured from compiled HLO on the virtual 8-CPU
    mesh (bytes are independent of row count: histogram grids are
    [A, F, B, 3])."""
    code = r"""
import sys, re
sys.path.insert(0, %r)
import numpy as np, jax, jax.numpy as jnp
from lightgbm_tpu.config import Config
from lightgbm_tpu.io.dataset import BinnedDataset
from lightgbm_tpu.io.device import to_device
from lightgbm_tpu.learner.serial import GrowthParams
from lightgbm_tpu.ops.split import SplitParams
from lightgbm_tpu.parallel.learners import build_tree_distributed
from lightgbm_tpu.parallel.mesh import make_mesh
rng = np.random.RandomState(0)
n, f = 65536, 28
X = rng.normal(size=(n, f)).astype(np.float32)
ds = BinnedDataset.from_raw(X, Config.from_params({"max_bin": 63}))
dd = to_device(ds)
grad = jnp.asarray(rng.normal(size=n).astype(np.float32))
hess = jnp.ones(n) * 0.25
p = GrowthParams(num_leaves=255, split=SplitParams(
    min_data_in_leaf=20, min_sum_hessian_in_leaf=1e-3))
mesh = make_mesh(8)
fn = jax.jit(lambda g, h: build_tree_distributed(
    mesh, "data", "data", dd, g, h, p, hist_backend="scatter"))
txt = fn.lower(grad, hess).compile().as_text()
print("HLO_TEXT_BYTES", len(txt))
import json
sys.stdout.write("COLLECTIVE_HLO_START\n")
# emit only collective op lines to keep the pipe small
for line in txt.splitlines():
    if ("all-reduce" in line or "all-gather" in line
            or "reduce-scatter" in line):
        print(line)
print("COLLECTIVE_HLO_END")
""" % ROOT
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if "host_platform_device_count" not in f]
    flags.append("--xla_force_host_platform_device_count=8")
    env["XLA_FLAGS"] = " ".join(flags)
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=3600)
    if "COLLECTIVE_HLO_START" not in r.stdout:
        raise RuntimeError(f"collective probe failed: {r.stderr[-2000:]}")
    body = r.stdout.split("COLLECTIVE_HLO_START")[1].split(
        "COLLECTIVE_HLO_END")[0]
    return _collective_bytes(body)


def main():
    table = {"cpu_baseline_row_iters_per_sec": round(
        CPU_BASELINE_ROW_ITERS, 1),
        "gpu_target_multiple_X": GPU_TARGET_MULTIPLE,
        "gpu_target_row_iters_per_sec": round(
            GPU_TARGET_MULTIPLE * CPU_BASELINE_ROW_ITERS, 1),
        "gpu_target_source": ("docs/GPU-Tutorial.rst:162 'over three times "
                              "speedup' (half-M60 vs its own 6-vCPU box) "
                              "taken vs the FULL 28-core baseline — the "
                              "aggressive reading; the docs' only other "
                              "bound is 'budget GPU ... faster than a "
                              "28-core Haswell' (GPU-Performance.rst:172), "
                              "i.e. >=1x")}
    # end-to-end first: a fresh device gives the representative number
    it_1m = iter_time(1_000_000)
    table["iter_s_1m"] = round(it_1m, 4)
    table["row_iters_per_sec_1m"] = round(1_000_000 / it_1m, 1)
    y_legs = [1_000_000 / it_1m]
    if os.environ.get("FULL", "0") == "1":
        it_full = iter_time(10_500_000)
        table["iter_s_10m5"] = round(it_full, 4)
        table["row_iters_per_sec_10m5"] = round(10_500_000 / it_full, 1)
        y_legs.append(10_500_000 / it_full)
    y = min(y_legs) / CPU_BASELINE_ROW_ITERS
    table["single_chip_multiple_Y"] = round(y, 3)
    table["needed_8chip_scaling_Z"] = round(GPU_TARGET_MULTIPLE / y, 2)

    peak = measured_peak()
    table.update(peak)
    print("peak:", peak, flush=True)
    table["wave_kernel"] = wave_times(peak["peak_int8_tmacs"])
    table["wave_kernel_note"] = (
        "ns_per_row is the 1M->4M dispatch-wall slope; dispatch-latency "
        "variance (~+-1 ms per point) puts ~+-0.3 ns/row error bars on "
        "it, so small-wave utilizations carry wide bars (values near or "
        "above 1.0 mean 'at the MXU roofline within measurement error', "
        "not >100%).  peak_*_tmacs itself under-reads ~5-10%: each "
        "chained step pays a clip+cast epilogue on the 67 MB product.")
    print("waves:", table["wave_kernel"], flush=True)

    B = collective_bytes_per_tree()
    table["allreduce_bytes_per_tree_B"] = B
    table["assumed_ici_effective_GBps"] = ICI_EFFECTIVE_GBPS
    t_ici = B / (ICI_EFFECTIVE_GBPS * 1e9)
    table["ici_s_per_tree"] = round(t_ici, 6)
    # per-chip compute for a 10.5M-row tree split 8 ways ~= the measured
    # 1M-row iteration (1.31M rows/chip; wave cost is ~linear in rows
    # above 1M — fixed overheads are the sub-linear part, so this
    # UNDERSTATES 8-chip efficiency slightly -> conservative)
    t_comp = it_1m * (10.5e6 / 8) / 1_000_000
    table["per_chip_compute_s_per_tree_C"] = round(t_comp, 4)
    eff = t_comp / (t_comp + t_ici)          # worst case: zero overlap
    table["projected_8chip_scaling_no_overlap"] = round(8 * eff, 2)
    proj = (10.5e6 / (t_comp + t_ici)) / CPU_BASELINE_ROW_ITERS
    table["projected_8chip_multiple"] = round(proj, 2)
    table["beats_gpu_target"] = bool(proj >= GPU_TARGET_MULTIPLE)
    table["recorded_on"] = "TPU v5e (bench device), round 5"

    os.makedirs(os.path.dirname(ARTIFACT), exist_ok=True)
    with open(ARTIFACT, "w") as f:
        json.dump(table, f, indent=1)
    print(json.dumps(table, indent=1))
    print("wrote", ARTIFACT)


if __name__ == "__main__":
    main()
