"""Worker for the 2-process multi-host seam test (run by
``tests/test_multihost.py``, one subprocess per rank).

Exercises the ONLY distributed components a single-process suite cannot:
``init_distributed`` (the rendezvous analog of the reference's YARN AM +
TCP-mesh handshake, `linkers_socket.cpp:27-68,225-274`) and
``jax_process_allgather`` (the DCN ingest collective,
`dataset_loader.cpp:860-880`), then trains one data-parallel tree over
the cross-process mesh and checks it equals the serial tree built from
the identical mappers on the full data.
"""
import hashlib
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"


def main():
    rank = int(sys.argv[1])
    port = sys.argv[2]
    world = 2

    import jax
    # a worker is a CPU process wherever it runs; config wins over env
    # (same as tests/conftest.py)
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import BinnedDataset
    from lightgbm_tpu.io.device import to_device
    from lightgbm_tpu.io.distributed import (find_bins_distributed,
                                             jax_process_allgather)
    from lightgbm_tpu.learner.serial import (GrowthParams, SplitParams,
                                             build_tree)
    from lightgbm_tpu.parallel.learners import build_tree_distributed
    from lightgbm_tpu.parallel.mesh import init_distributed

    # --- rendezvous (linkers_socket.cpp:27-68 analog) -------------------
    init_distributed(f"localhost:{port}", num_processes=world,
                     process_id=rank)
    assert jax.process_count() == world, jax.process_count()
    assert len(jax.devices()) == world, jax.devices()

    # --- mod-rank row shard (dataset_loader.cpp:639-742) ----------------
    rng = np.random.RandomState(0)
    n, F = 1024, 6
    X = rng.normal(size=(n, F)).astype(np.float32)
    y = (X[:, 0] * 2 + X[:, 1] + 0.2 * rng.normal(size=n)).astype(np.float32)
    rows = np.arange(rank, n, world)
    X_local, y_local = X[rows], y[rows]

    # --- distributed bin finding over the DCN allgather -----------------
    cfg = Config.from_params({"max_bin": 63})
    mappers = find_bins_distributed(X_local, cfg, rank, world,
                                    jax_process_allgather)
    digest = hashlib.sha1(json.dumps(
        [m.to_dict() for m in mappers], sort_keys=True).encode()).hexdigest()
    digests = jax_process_allgather(digest)
    assert len(set(digests)) == 1, "mappers differ across ranks"

    # --- one data-parallel tree over the cross-process mesh -------------
    ds_local = BinnedDataset.from_raw(X_local, cfg, mappers=mappers)
    dd = to_device(ds_local)
    grad_local = jnp.asarray(-(y_local - y.mean()))
    hess_local = jnp.ones(len(rows))

    mesh = Mesh(np.array(jax.devices()), ("data",))
    shard = NamedSharding(mesh, P("data"))
    repl = NamedSharding(mesh, P())

    def globalize(x, sharded):
        x = np.asarray(x)
        if sharded:
            return jax.make_array_from_process_local_data(shard, x)
        return jax.device_put(x, repl)

    # bins/grad/hess are row-sharded (each process contributes its rows);
    # the [F]-indexed metadata is identical everywhere -> replicated
    dd_g = dd._replace(
        bins=globalize(dd.bins, True),
        bin_offsets=globalize(dd.bin_offsets, False),
        num_bins=globalize(dd.num_bins, False),
        default_bins=globalize(dd.default_bins, False),
        missing_types=globalize(dd.missing_types, False),
        is_categorical=globalize(dd.is_categorical, False),
        nan_bins=globalize(dd.nan_bins, False),
        feat_group=globalize(dd.feat_group, False),
        feat_offset=globalize(dd.feat_offset, False))
    p = GrowthParams(num_leaves=15, split=SplitParams(
        min_data_in_leaf=10, min_sum_hessian_in_leaf=0.0))
    dist = build_tree_distributed(
        mesh, "data", "data", dd_g,
        globalize(grad_local, True), globalize(hess_local, True), p)

    # --- serial oracle: same mappers, full data, one process ------------
    ds_full = BinnedDataset.from_raw(X, cfg, mappers=mappers)
    grad = jnp.asarray(-(y - y.mean()))
    serial = build_tree(to_device(ds_full), grad, jnp.ones(n), p)

    assert int(jax.device_get(dist.num_leaves)) == int(serial.num_leaves)
    np.testing.assert_array_equal(np.asarray(jax.device_get(dist.feature)),
                                  np.asarray(serial.feature))
    np.testing.assert_array_equal(
        np.asarray(jax.device_get(dist.threshold_bin)),
        np.asarray(serial.threshold_bin))
    print(f"MULTIHOST_OK rank={rank}")


if __name__ == "__main__":
    main()
