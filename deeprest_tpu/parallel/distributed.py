"""Multi-host bring-up: ``jax.distributed`` over DCN, one global mesh.

The reference's only distribution is app-plane RPC (Thrift/AMQP/redis —
SURVEY.md §5.8); its ML core is strictly single-device.  The multi-host
tier here follows the TPU-native recipe instead of translating an
NCCL/MPI design:

- every host runs the SAME single-controller program and calls
  :func:`initialize_distributed` first — a no-op for single-process runs,
  so one code path serves laptop, single chip, and pod;
- after initialization ``jax.devices()`` is the GLOBAL device set; the
  (data, expert, model) mesh is laid over it with **data outermost** so
  the per-step gradient all-reduce crosses DCN once while expert/model
  collectives (the mixing sum, TP reductions) stay on intra-slice ICI
  (the "collectives ride ICI, not DCN" rule);
- each host feeds only its own shard of the global batch
  (:func:`process_batch_slice` + :func:`feed_global_batch`), the standard
  single-controller data path (``jax.make_array_from_process_local_data``).

Single-process tests exercise all of this on the virtual CPU mesh; the
arithmetic (slicing, axis layout) is process-count-parameterized so the
multi-host math is testable without multiple hosts.
"""

from __future__ import annotations

import os
from typing import Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeprest_tpu.config import MeshConfig
from deeprest_tpu.parallel.mesh import AXES, make_mesh


def initialize_distributed(coordinator_address: str | None = None,
                           num_processes: int | None = None,
                           process_id: int | None = None) -> bool:
    """Join the multi-host job if one is configured; returns whether it was.

    Configuration comes from the arguments or the standard environment
    (``JAX_COORDINATOR_ADDRESS``, ``JAX_NUM_PROCESSES``, ``JAX_PROCESS_ID``;
    on TPU pods ``jax.distributed.initialize()`` auto-discovers all three
    from the metadata server, so bare ``initialize_distributed()`` works
    there too).  With no configuration at all this is a no-op returning
    False — single-process runs never pay for the distributed service.
    """
    env = os.environ
    coordinator_address = (coordinator_address
                           or env.get("JAX_COORDINATOR_ADDRESS") or None)
    if num_processes is None and env.get("JAX_NUM_PROCESSES"):
        num_processes = int(env["JAX_NUM_PROCESSES"])
    if process_id is None and env.get("JAX_PROCESS_ID"):
        process_id = int(env["JAX_PROCESS_ID"])
    if coordinator_address is None and num_processes is None:
        return False
    jax.distributed.initialize(coordinator_address=coordinator_address,
                               num_processes=num_processes,
                               process_id=process_id)
    return True


def global_mesh(config: MeshConfig | None = None,
                devices: Sequence[jax.Device] | None = None) -> Mesh:
    """The (data, expert, model) mesh over the global device set.

    A documentation-carrying alias of :func:`make_mesh` (same defaults):
    after :func:`initialize_distributed`, ``jax.devices()`` is global, and
    the C-order reshape puts the **data axis outermost** — it strides
    across whole hosts, so the gradient all-reduce crosses DCN while
    expert/model collectives stay on intra-host ICI.  The default config
    (data = every device) is the DP north-star layout.
    """
    return make_mesh(config, devices=devices)


def process_batch_slice(global_batch: int,
                        process_index: int | None = None,
                        process_count: int | None = None) -> slice:
    """This process's contiguous slice of the global batch axis.

    The global batch must divide evenly — a ragged split would desync the
    compiled step's static shapes across hosts.
    """
    if process_index is None:
        process_index = jax.process_index()
    if process_count is None:
        process_count = jax.process_count()
    if global_batch % process_count != 0:
        raise ValueError(
            f"global batch {global_batch} not divisible by "
            f"{process_count} processes")
    per = global_batch // process_count
    return slice(process_index * per, (process_index + 1) * per)


def _feed_data_sharded(mesh: Mesh, arr: np.ndarray,
                       axes: tuple[str | None, ...]) -> jax.Array:
    """The ONE per-host feed path: slice this process's contiguous chunk
    of the ``data``-sharded axis and let
    ``jax.make_array_from_process_local_data`` stitch the global array.

    Under one process the local slice IS the global array, so the virtual
    CPU mesh exercises the exact multi-process assembly code (not a
    device_put twin of it) — no host ever ships another host's rows to
    its devices, and there is no second code path to drift.
    """
    ax = axes.index("data")
    n = int(arr.shape[ax])
    data_size = int(mesh.shape["data"])
    if n % data_size != 0:
        # device_put would raise an opaque GSPMD shape error here — and
        # older jax versions silently REPLICATED the batch instead of
        # sharding it (8x the per-device memory and a wrong-throughput
        # measurement, never a wrong result).  Fail loudly with the fix:
        # the trainer's _batches/_epoch_plan already pad ragged batches
        # with zero-weight rows, so a divisible batch size is one config
        # knob away.
        raise ValueError(
            f"batch axis {ax} of shape {tuple(arr.shape)} has {n} rows, "
            f"not divisible by the mesh data axis ({data_size} shards); "
            "pad the batch to a multiple with zero-weight rows (the "
            "trainer's _batches wrap-padding) or pick a batch size "
            "divisible by MeshConfig.data")
    # graftlint: disable=JX005 -- designed feed-path site: batch/plan arrays are constructed here from the table-owned axis names, not per-leaf state specs
    sharding = NamedSharding(mesh, P(*axes))
    sel = (slice(None),) * ax + (process_batch_slice(n),)
    return jax.make_array_from_process_local_data(
        sharding, np.ascontiguousarray(arr[sel]))


def feed_global_batch(mesh: Mesh, global_batch: np.ndarray,
                      axes: tuple[str | None, ...] | None = None) -> jax.Array:
    """Turn the host-side GLOBAL batch into the global data-sharded array.

    Every process passes the same ``global_batch`` view (deterministic
    selection keeps them identical across hosts); each keeps only its
    :func:`process_batch_slice` of the ``data`` axis and the
    per-host assembly (:func:`_feed_data_sharded`) stitches the global
    array.  A batch axis not divisible by the mesh's data-axis size
    raises immediately (it used to silently replicate on older jax).
    """
    if axes is None:
        axes = ("data",) + (None,) * (global_batch.ndim - 1)
    return _feed_data_sharded(mesh, np.asarray(global_batch), axes)


def feed_replicated(mesh: Mesh, arr: np.ndarray) -> jax.Array:
    """A fully-replicated global array from identical per-process data
    (eval/predict inputs: every process holds the same windows)."""
    # graftlint: disable=JX005 -- designed feed-path site: replicated input placement, not a per-leaf state spec
    sharding = NamedSharding(mesh, P())
    if jax.process_count() == 1:
        return jax.device_put(arr, sharding)
    return jax.make_array_from_process_local_data(sharding, np.asarray(arr))


def feed_global_coo(mesh: Mesh, cols: np.ndarray, vals: np.ndarray,
                    axes: tuple[str | None, ...] | None = None
                    ) -> tuple[jax.Array, jax.Array]:
    """The padded-COO twin of :func:`feed_global_batch`: ship a sparse
    ``(cols[..., K], vals[..., K])`` window batch data-sharded over the
    mesh.

    Both halves shard identically along the leading (batch) axis so a
    row's columns and values land on the same shard; the divisibility
    contract (and its loud error) is :func:`_feed_data_sharded`'s.  At
    the 10k-endpoint width this is the ~F/(2K) host→device byte saving
    the sparse-first pipeline exists for (ops/densify.py densifies on
    device inside the consuming executable).
    """
    cols = np.asarray(cols)
    vals = np.asarray(vals)
    if cols.shape != vals.shape:
        raise ValueError(
            f"padded-COO halves disagree: cols {cols.shape} vs "
            f"vals {vals.shape}")
    if axes is None:
        axes = ("data",) + (None,) * (cols.ndim - 1)
    return (_feed_data_sharded(mesh, cols, axes),
            _feed_data_sharded(mesh, vals, axes))


def stage_sparse_base(mesh: Mesh, cols: np.ndarray, vals: np.ndarray,
                      mn: np.ndarray, rg: np.ndarray, capacity: int,
                      live: np.ndarray | None = None):
    """Replicated device residency for a padded-COO BASE series plus its
    normalization stats — the sparse twin of the trainer's staged dense
    base (every process holds the same rows; per-step feeds are then just
    ``[B]`` start indices).  Returns an ``ops.densify.SparseBase`` whose
    static ``capacity`` the consuming jit treats as a compile-time
    constant.  Stats ride as device arrays (runtime ARGUMENTS — baked
    constants would let XLA strength-reduce the normalize divide and
    break bit parity; the serve/fused.py lesson).  With ``live`` (the
    table of ``ops.densify.compact_table``) the base is staged in the
    compact form: ``cols`` as ranks in the table, the statistics at it."""
    from deeprest_tpu.ops.densify import SparseBase, compact_rows

    cols = np.asarray(cols, np.int32)
    mn, rg = np.asarray(mn, np.float32), np.asarray(rg, np.float32)
    if live is not None:
        cols = compact_rows(cols, vals, live)
        mn, rg = (a if a.size == 1 else a[live] for a in (mn, rg))
        live = feed_replicated(mesh, np.asarray(live, np.int32))
    return SparseBase(
        cols=feed_replicated(mesh, cols),
        vals=feed_replicated(mesh, np.asarray(vals, np.float32)),
        mn=feed_replicated(mesh, mn), rg=feed_replicated(mesh, rg),
        live=live, capacity=int(capacity))


def prefetch_to_device(mesh: Mesh, batches, depth: int = 2):
    """Overlap host→device transfer with device compute.

    ``batches`` yields tuples of host numpy arrays; each is fed through
    :func:`feed_global_batch` immediately (device transfers are
    asynchronous), and up to ``depth`` fed batches are kept in flight ahead
    of the consumer — so the copy of batch t+1 proceeds while the step on
    batch t executes.  ``depth=0`` degenerates to synchronous per-batch
    feeding.  Order is preserved exactly, so training is bit-identical with
    or without prefetch.
    """
    import collections

    queue: collections.deque = collections.deque()
    for batch in batches:
        queue.append(tuple(feed_global_batch(mesh, np.asarray(a))
                           for a in batch))
        if len(queue) > depth:
            yield queue.popleft()
    while queue:
        yield queue.popleft()


def stage_plan(mesh: Mesh, starts: np.ndarray,
               weights: np.ndarray) -> tuple[jax.Array, jax.Array]:
    """Ship an epoch's superstep batch plan to device memory once.

    ``starts``/``weights`` are ``[C, S, B]`` (chunks × steps-per-superstep
    × batch) host arrays from ``Trainer._epoch_plan``.  The batch axis is
    the TRAILING one and shards over the mesh's ``data`` axis — the
    in-step gather then produces a data-sharded window batch, keeping the
    superstep data-parallel exactly like the per-step indexed feed.  On a
    pod every process passes the same (rng-deterministic) global plan and
    keeps only its batch slice, mirroring :func:`feed_global_batch`'s
    contract for the leading axis.
    """
    def ship(a: np.ndarray) -> jax.Array:
        axes = (None,) * (a.ndim - 1) + ("data",)
        return _feed_data_sharded(mesh, np.asarray(a), axes)

    return ship(np.asarray(starts)), ship(np.asarray(weights))


def gather_to_host(arr: jax.Array) -> np.ndarray:
    """A numpy copy of a possibly cross-host-sharded array on every host
    (eval predictions feeding the host-side MAE report)."""
    if jax.process_count() == 1:
        return np.asarray(arr)
    from jax.experimental import multihost_utils

    return np.asarray(multihost_utils.process_allgather(arr, tiled=True))


__all__ = [
    "AXES",
    "initialize_distributed",
    "global_mesh",
    "process_batch_slice",
    "feed_global_batch",
    "feed_global_coo",
    "feed_replicated",
    "stage_sparse_base",
    "prefetch_to_device",
    "stage_plan",
    "gather_to_host",
]
