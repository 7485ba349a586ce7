"""Device mesh management — the communication backend seam.

Replaces the reference's network stack (`/root/reference/src/network/`:
socket/MPI linkers, Bruck/recursive-halving/ring collectives,
`network.cpp:64-243`) with JAX device meshes and XLA collectives over
ICI/DCN.  The reference's pluggable-collective hook
(``LGBM_NetworkInitWithFunctions``, `c_api.h:760`) maps to this module:
every distributed learner takes a ``MeshContext`` and calls
``psum``-style collectives inside ``shard_map``; tests inject a virtual
8-device CPU mesh (`XLA_FLAGS=--xla_force_host_platform_device_count=8`).

Multi-host: ``init_distributed`` wraps ``jax.distributed.initialize`` —
the coordinator-address pattern is the TPU-native equivalent of the
fork's YARN application-master rendezvous (`linkers_socket.cpp:27-68`:
workers report to an AM address and receive the machine list; here the
coordinator does the same via the JAX distributed service).
"""
from __future__ import annotations

import os
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..config import Config
from ..utils.log import log_info, log_warning


def init_distributed(coordinator_address: Optional[str] = None,
                     num_processes: Optional[int] = None,
                     process_id: Optional[int] = None) -> None:
    """Multi-host rendezvous (reference: YARN AM rendezvous + TCP mesh
    handshake, linkers_socket.cpp:27-68,225-274).  On TPU pods the
    environment usually auto-detects; explicit args mirror the
    ``application_master_address`` config of the fork."""
    kwargs = {}
    if coordinator_address:
        kwargs["coordinator_address"] = coordinator_address
    if num_processes is not None:
        kwargs["num_processes"] = num_processes
    if process_id is not None:
        kwargs["process_id"] = process_id

    from ..obs import span
    from ..utils.faults import fault_point
    from ..utils.retry import RetryPolicy, retry_call

    def _connect():
        # injection seam for the rendezvous handshake (the fault the
        # fork's YARN workers see when the AM isn't up yet)
        fault_point("rendezvous.connect")
        jax.distributed.initialize(**kwargs)

    from ..obs.flight_recorder import record as fr_record
    fr_record("parallel.mesh.rendezvous", "distributed.initialize")
    from ..obs.telemetry import hold_trace, release_trace
    try:
        # retried with backoff: at pod startup the coordinator may come
        # up seconds after the workers (the reference's socket Connect
        # loops with time_out retries, linkers_socket.cpp:225-274).
        # Trace records buffer until the rendezvous resolves this
        # process's rank — the per-rank trace file must not open as
        # rank 0 on every worker.
        hold_trace()
        try:
            with span("mesh.rendezvous"):
                retry_call(_connect, policy=RetryPolicy.from_env(),
                           what="rendezvous.connect")
        finally:
            release_trace()
    except RuntimeError as exc:
        # idempotent entry: a second initialize (a caller that does not
        # ask jax.distributed.is_initialized() first, as the CLI does)
        # is a no-op, not a crash (ADVICE r4).  jax 0.9 phrases it
        # "distributed.initialize should only be called once."
        if "only be called once" not in str(exc).lower():
            raise


def init_distributed_from_machines(machines: str, local_listen_port: int,
                                   num_machines: int) -> None:
    """LGBM_NetworkInit semantics (c_api.h:749-756): a comma-separated
    ``ip:port`` machine list.  The reference resolves its own rank by
    matching a local endpoint against the list and TCP-meshes everyone
    (`linkers_socket.cpp:97-107,225-274`); here the first machine is the
    ``jax.distributed`` coordinator and rank = list position, matched by
    the local listen port (all-loopback lists work for tests)."""
    entries = [m.strip() for m in machines.replace("\n", ",").split(",")
               if m.strip()]
    if num_machines > len(entries):
        raise ValueError(
            f"num_machines={num_machines} but machine list has "
            f"{len(entries)} entries")
    entries = entries[:num_machines]
    import socket

    def _is_local_ip(host: str) -> bool:
        """Bindability test — the reference resolves its local endpoint by
        actually binding a socket (`linkers_socket.cpp:20-78`), which works
        where hostname DNS lies (Debian's 127.0.1.1 /etc/hosts entry)."""
        if host in ("127.0.0.1", "localhost", "0.0.0.0"):
            return True
        try:
            s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
            try:
                s.bind((host, 0))
                return True
            finally:
                s.close()
        except OSError:
            return False

    # rank = the local entry; when several entries are local (all-loopback
    # test lists), the listen port disambiguates — port matching only
    # applies AMONG local entries, else the shared-port multi-host setup
    # (every machine listening on the same port) would resolve rank 0
    # everywhere
    local = [i for i, e in enumerate(entries)
             if _is_local_ip(e.rsplit(":", 1)[0])]
    if len(local) == 1:
        rank = local[0]
    else:
        cands = local if local else range(len(entries))
        matches = [i for i in cands
                   if ":" in entries[i]
                   and int(entries[i].rsplit(":", 1)[1]) == local_listen_port]
        if len(matches) != 1:
            raise ValueError(
                "cannot resolve local rank from machine list "
                f"{entries!r} with local_listen_port={local_listen_port}")
        rank = matches[0]
    init_distributed(coordinator_address=entries[0],
                     num_processes=num_machines, process_id=rank)


class ProcessRows:
    """Block layout of mod-rank-sharded local rows inside global
    row-sharded arrays (multi-process data/voting-parallel training).

    Each process contributes ONE padded block of the global row axis:
    ``[rank*per, rank*per + n_local)`` are its real rows, the rest of
    the block is padding (masked out-of-bag).  The reference's
    equivalent is each machine's local row range after mod-rank
    sharding (`dataset_loader.cpp:639-742`)."""

    def __init__(self, mesh_ctx: "MeshContext", n_local: int):
        from ..io.distributed import jax_process_allgather
        self.mesh_ctx = mesh_ctx
        self.world = jax.process_count()
        self.counts = [int(x) for x in jax_process_allgather(int(n_local))]
        self.n_local = int(n_local)
        self.n_global = sum(self.counts)
        ld = jax.local_device_count()
        # per-process block: covers the largest local shard, divisible
        # by the local device count so every device shard is equal
        self.per = -(-max(self.counts) // ld) * ld
        self.n_pad = self.per * self.world

    def globalize(self, local: np.ndarray, fill=0) -> jax.Array:
        """``[n_local, ...] -> global [n_pad, ...]`` row-sharded array."""
        local = np.asarray(local)
        block = np.full((self.per,) + local.shape[1:], fill, local.dtype)
        block[:len(local)] = local
        return jax.make_array_from_process_local_data(
            self.mesh_ctx.row_sharding(), block)

    def replicate(self, x) -> jax.Array:
        return jax.device_put(np.asarray(x), self.mesh_ctx.replicated())

    def valid_mask_local(self) -> np.ndarray:
        m = np.zeros(self.per, bool)
        m[:self.n_local] = True
        return m

    def local_np(self, global_arr) -> np.ndarray:
        """This process's REAL rows of a global row-sharded array.
        Shards are DEDUPED by row offset: on a 2-D (data x feature)
        mesh the feature-axis devices hold row replicas."""
        by_start = {}
        for s in global_arr.addressable_shards:
            by_start.setdefault(s.index[0].start or 0, s.data)
        block = np.concatenate(
            [np.asarray(by_start[k]) for k in sorted(by_start)])
        return block[:self.n_local]


class MeshContext:
    """A 1-D (data) or 2-D (data × feature) device mesh + shard helpers.

    All placement decisions flow through the partition-rule registry
    (``parallel/partition.py``): ``partition_rules()`` is the rule
    table for this mesh's learner type, ``sharding_for(name)`` resolves
    one persistent name, and ``place_data``/``place_scores``/
    ``place_valid`` place whole state groups — an array name without a
    rule raises instead of inheriting a default layout."""

    def __init__(self, config: Config, devices: Optional[Sequence] = None):
        self.config = config
        devices = list(devices if devices is not None else jax.devices())
        shape = tuple(config.mesh_shape) or (len(devices),)
        n_mesh = int(np.prod(shape))
        if n_mesh > len(devices):
            raise ValueError(
                f"mesh_shape {shape} needs {n_mesh} devices, have "
                f"{len(devices)}")
        devices = devices[:n_mesh]
        self.data_axis = config.data_axis_name
        self.feature_axis = config.feature_axis_name
        # set by the booster once it knows the row count: whether the
        # running scores are sharded with the rows (partition.train_rules)
        self.scores_sharded = False
        if len(shape) == 1:
            self.mesh = Mesh(np.asarray(devices).reshape(shape),
                             (self.data_axis,))
            self.axis_names: Tuple[str, ...] = (self.data_axis,)
        elif len(shape) == 2:
            self.mesh = Mesh(np.asarray(devices).reshape(shape),
                             (self.data_axis, self.feature_axis))
            self.axis_names = (self.data_axis, self.feature_axis)
        else:
            raise ValueError("mesh_shape must have 1 or 2 axes")

    @property
    def num_data_shards(self) -> int:
        return self.mesh.shape[self.data_axis]

    @property
    def num_feature_shards(self) -> int:
        return (self.mesh.shape[self.feature_axis]
                if self.feature_axis in self.mesh.shape else 1)

    @property
    def row_sharded(self) -> bool:
        """Whether this mesh's learner type shards the row axis
        (data/voting) or replicates rows (feature-parallel)."""
        return self.config.tree_learner in ("data", "voting")

    def partition_rules(self):
        """The partition-rule table governing every persistent array
        placed on THIS mesh (see ``parallel/partition.py``)."""
        from .partition import train_rules
        return train_rules(self.data_axis, self.row_sharded,
                           self.scores_sharded)

    def sharding_for(self, name: str) -> NamedSharding:
        """Resolve one persistent array name through the registry —
        an unmatched name raises ``PartitionRuleError``."""
        from .partition import match_name
        return NamedSharding(self.mesh,
                             match_name(self.partition_rules(), name))

    def row_sharding(self) -> NamedSharding:
        """[n, ...] arrays sharded over rows."""
        return NamedSharding(self.mesh, P(self.data_axis))

    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def place_data(self, dd, row_sharded: Optional[bool] = None):
        """Place a DeviceData ONCE under the partition-rule registry:
        ``data/bins`` sharded over the data axis rows (replicated for
        feature-parallel, which replicates rows), every ``data/<meta>``
        array replicated.  Without this, each jitted distributed build
        re-lays-out the single-device store to the mesh per dispatch —
        at the 10.5M-row HIGGS shape that is a ~294 MB reshard of the
        biggest buffer EVERY iteration.  The pjit shard-rule pattern of
        SNIPPETS.md [1]/[2] (fmengine / EasyDeL trainers place params
        once, then every step consumes them in place) applied to the
        GBDT training store."""
        from ..io.device import DeviceData
        from .partition import device_data_names, place_tree, train_rules
        children, aux = dd.tree_flatten()
        rules = (self.partition_rules() if row_sharded is None
                 else train_rules(self.data_axis, row_sharded))
        placed = place_tree(rules, self.mesh,
                            {"data": device_data_names(dd)})["data"]
        fields = type(dd)._fields
        return DeviceData(*(placed[f] for f in fields[:len(children)]), *aux)

    def place_scores(self, scores) -> jax.Array:
        """Place a running score state under the registry's ``scores``
        rule: with the rows where ``scores_sharded``, else replicated
        (the row count is the unpadded n)."""
        return jax.device_put(scores, self.sharding_for("scores"))

    def place_valid(self, i: int, dd, scores):
        """Place valid set ``i``'s DeviceData + running scores under
        the ``valid/<i>/...`` rules (all replicated)."""
        from ..io.device import DeviceData
        from .partition import device_data_names, place_tree
        tree = {"valid": {str(i): {"data": device_data_names(dd),
                                   "scores": scores}}}
        placed = place_tree(self.partition_rules(), self.mesh,
                            tree)["valid"][str(i)]
        children, aux = dd.tree_flatten()
        fields = type(dd)._fields
        dd_placed = DeviceData(
            *(placed["data"][f] for f in fields[:len(children)]), *aux)
        return dd_placed, placed["scores"]

    def pad_rows(self, n: int) -> int:
        """Rows padded to a multiple of the data-shard count."""
        d = self.num_data_shards
        return (n + d - 1) // d * d


def shard_row_ranges(n: int, num_shards: int):
    """The mesh row partition as explicit ``[(lo, hi), ...]`` global
    ranges — the SAME contiguous equal-length layout ``pad_rows`` +
    row sharding produce (shard ``d`` owns rows ``[d*per, (d+1)*per)``
    of the padded space).  The streamed out-of-core trainer
    (``boosting/streaming.py``) assigns blocks to shards through this,
    which is what makes per-rank shard ownership compose with mesh row
    sharding: streamed shard folds cover exactly the rows the
    in-memory data-parallel mesh places on each device."""
    d = max(1, num_shards)
    per = (n + d - 1) // d
    return [(i * per, (i + 1) * per) for i in range(d)]


def make_mesh(num_devices: int, axis: str = "data",
              devices: Optional[Sequence] = None) -> Mesh:
    devices = list(devices if devices is not None else jax.devices())
    return Mesh(np.asarray(devices[:num_devices]), (axis,))
