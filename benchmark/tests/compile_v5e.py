#!/usr/bin/env python3
"""Compile each cell's iteration for a described ``v5e:2x2`` chip at its
real shape, with no chip attached: what the chip's compiler refuses
(VMEM, tiling, memory) shows here at no chip time.

    JAX_PLATFORMS=cpu python3 benchmark/tests/compile_v5e.py [cell ...]

It compiles the body of the block program ``GBDT._make_block_fn`` scans:
objective gradients, ``build_tree`` on the default TPU backend in the
configuration's precision, the score update.  (The program closes its
labels into the block as a constant, so its own jitted block cannot be
lowered from shapes alone.)  A compile that passes is not a chip run.
A script and not a test: only one process may load the TPU library, and
the repo's tests already have the one file that does.
"""
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(cells) -> int:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    jax.config.update("jax_enable_compilation_cache", False)
    from lightgbm_tpu.io.device import DeviceData
    from lightgbm_tpu.learner import serial
    from lightgbm_tpu.learner.serial import GrowthParams, build_tree
    from lightgbm_tpu.ops.split import SplitParams
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    jax.default_backend = lambda: "tpu"        # the program's TPU branch

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    files = {c["name"]: c["file"] for c in bench["configs"]}
    for w in bench["workloads"]:
        if cells and w["name"] not in cells:
            continue
        with open(os.path.join(ROOT, files[w["config"]])) as f:
            cfg = json.load(f)
        n, F = cfg["data"]["rows"], cfg["data"]["features"]
        p = cfg["params"]
        mb = p["max_bin"]
        mode = cfg["precision"]["hist_mode"]
        meta = lambda: s((F,), jnp.int32)               # noqa: E731
        dd = DeviceData(
            bins=s((n, F), jnp.uint8), bin_offsets=meta(), num_bins=meta(),
            default_bins=meta(), missing_types=meta(),
            is_categorical=s((F,), jnp.bool_), nan_bins=meta(),
            feat_group=meta(), feat_offset=meta(), total_bins=F * mb,
            max_bins=mb, has_categorical=False, max_group_bins=mb,
            is_bundled=False, has_missing=False)
        growth = GrowthParams(
            num_leaves=p["num_leaves"], max_depth=-1, wave_size=0,
            split=SplitParams(min_data_in_leaf=p["min_data_in_leaf"],
                              min_sum_hessian_in_leaf=1e-3))
        backend = serial.resolve_backend(dd, p["num_leaves"], hist_mode=mode)

        def iteration(dd, bins_t, scores, y, lr):
            prob = jax.nn.sigmoid(scores)
            bt = build_tree(dd, prob - y, prob * (1.0 - prob), growth,
                            bins_t=bins_t, hist_mode=mode)
            step = (lr * bt.row_value if bt.row_value.shape[0]
                    else (lr * bt.leaf_value)[bt.row_leaf])
            return scores + step, bt._replace(row_leaf=bt.row_leaf[:0],
                                              row_value=bt.row_value[:0])

        t0 = time.perf_counter()
        from lightgbm_tpu.ops.pallas_histogram import transpose_bins
        bt_shape = jax.eval_shape(transpose_bins, dd.bins).shape
        compiled = jax.jit(iteration, donate_argnums=(2,)).lower(
            dd, s(bt_shape, jnp.uint8), s((n,), jnp.float32),
            s((n,), jnp.float32), s((), jnp.float32)).compile()
        mem = compiled.memory_analysis()
        mib = lambda b: b / 2 ** 20                     # noqa: E731
        print(f"{w['name']}: {n} x {F}, {mb} bins, {p['num_leaves']} leaves, "
              f"{mode}: backend {backend}, compiled for "
              f"{topo.devices[0].device_kind} in "
              f"{time.perf_counter() - t0:.0f} s, "
              f"{compiled.as_text().count('tpu_custom_call')} "
              f"tpu_custom_call; args {mib(mem.argument_size_in_bytes):.0f} "
              f"MiB + temp {mib(mem.temp_size_in_bytes):.0f} MiB + out "
              f"{mib(mem.output_size_in_bytes):.0f} MiB (alias "
              f"{mib(mem.alias_size_in_bytes):.0f} MiB)", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
