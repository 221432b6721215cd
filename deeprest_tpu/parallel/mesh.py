"""Logical device mesh over TPU ICI.

The reference's ML core is single-device (reference:
resource-estimation/estimate.py:10 — one cuda/cpu pick, no DDP/NCCL
anywhere); distribution is *introduced* here the TPU way: one logical mesh
with three axes, all parallelism expressed as sharding annotations, all
collectives inserted by the GSPMD partitioner and riding ICI.

Axes (SURVEY.md §2.5):
- ``data``   — batch dimension (DP; gradient all-reduce over ICI),
- ``expert`` — the stacked per-metric experts (EP; the only cross-expert
  dataflow is the mixing sum, one all-reduce over this axis),
- ``model``  — the call-path feature dimension of the mask/GRU input
  projections (TP; pressure point when |M| reaches 10k endpoints).

Pipeline and sequence axes are deliberately absent: window length is 60 and
the recurrent core is the reference's long-context answer (SURVEY.md §5.7).
"""

from __future__ import annotations

from typing import Sequence

import jax
import numpy as np
from jax.sharding import Mesh

from deeprest_tpu.config import MeshConfig

AXES = ("data", "expert", "model")


class NoValidMeshError(RuntimeError):
    """No mesh shape fits the surviving devices (elastic remeshing):
    the expert/model axes are load-bearing — shrinking them would
    re-partition parameters mid-run — so when ``expert * model`` devices
    no longer exist there is nothing left to rebuild onto.  The caller
    (the trainer's fault barrier) surfaces this typed error instead of
    respinning."""


def shrink_mesh_config(config: MeshConfig, healthy_count: int) -> MeshConfig:
    """The largest valid mesh on ``healthy_count`` devices: shrink the
    DATA axis first, preserve expert/model.

    The data axis is the safe one to fold — batch rows redistribute and
    the gradient all-reduce simply spans fewer shards — while the
    expert/model axes encode the parameter partitioning the rule table
    placed.  The new data extent is the largest **divisor** of the old
    one that fits: divisor, not just ≤, so a batch size divisible by the
    old data axis stays divisible by the new one (the
    ``feed_global_batch`` contract survives the shrink — 8→4→2→1, never
    8→7).  Raises :class:`NoValidMeshError` when even ``data=1`` does
    not fit (fewer than ``expert * model`` healthy devices).
    """
    if healthy_count < 1:
        raise NoValidMeshError(
            f"no healthy devices remain (mesh was "
            f"{config.data}x{config.expert}x{config.model})")
    em = config.expert * config.model
    if em > healthy_count:
        raise NoValidMeshError(
            f"only {healthy_count} healthy device(s) remain but the "
            f"expert*model plane needs {em} "
            f"({config.expert}x{config.model}); the expert/model axes "
            "carry the parameter partitioning and cannot shrink in-run")
    budget = healthy_count // em
    d = next(d for d in range(min(config.data, budget), 0, -1)
             if config.data % d == 0)
    return MeshConfig(data=d, expert=config.expert, model=config.model)


def mesh_config_of(mesh: Mesh) -> MeshConfig:
    """The :class:`MeshConfig` a live mesh was (or could have been)
    built from — the shrink computation's input when a trainer holds
    only the constructed mesh."""
    return MeshConfig(data=int(mesh.shape["data"]),
                      expert=int(mesh.shape["expert"]),
                      model=int(mesh.shape["model"]))


def make_mesh(config: MeshConfig | None = None, devices: Sequence[jax.Device] | None = None) -> Mesh:
    """Build the (data, expert, model) mesh.

    Defaults to all available devices on the data axis when no config is
    given; a 1×1×1 config is a valid single-device mesh, so the trainer uses
    one code path everywhere.
    """
    if devices is None:
        devices = jax.devices()
    if config is None:
        config = MeshConfig(data=len(devices))
    if config.size > len(devices):
        raise ValueError(
            f"mesh {config.data}x{config.expert}x{config.model} needs "
            f"{config.size} devices, only {len(devices)} available"
        )
    grid = np.asarray(devices[: config.size]).reshape(
        config.data, config.expert, config.model
    )
    return Mesh(grid, AXES)


def data_ring(mesh: Mesh) -> tuple[int, ...]:
    """The indices of the mesh's ``data`` axis in an order in which each is
    a neighbour of the next over ICI, and the last of the first: the ring a
    hand-written ``ppermute`` should walk.  The partitioner's collectives
    know the topology; a permute goes where it is told, and between chips
    that share no link it takes two and shares them with another hop.

    Where the devices say where they sit (a TPU's ``coords``) that is
    column ``x`` = 0 upwards in ``y``, then the next column downwards, and
    so on: a ring wherever the chips form two columns (a v5e 2x2, whose
    ``jax.devices()`` order 0, 1, 2, 3 is NOT one: 1 and 2 lie across the
    diagonal) and a path with one long hop on wider slices.  Elsewhere (the
    CPU's virtual devices) the axis's own order."""
    devices = mesh.devices.reshape(mesh.shape["data"], -1)[:, 0]
    coords = [getattr(d, "coords", None) for d in devices]
    if any(c is None for c in coords):
        return tuple(range(len(devices)))

    def snake(i):
        x, y, *rest = coords[i]
        return (*rest, x, y if x % 2 == 0 else -y)

    return tuple(sorted(range(len(devices)), key=snake))
