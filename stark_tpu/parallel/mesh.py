"""Device mesh + data-sharding helpers.

The TPU-native replacement for the reference's Spark data layer (SURVEY.md §2
layer E): the N-row dataset is laid out once across the "data" mesh axis and
stays resident in HBM; chains are laid out across the "chains" axis.  All
cross-device communication is XLA collectives over ICI/DCN (psum of per-shard
log-likelihood partial sums — SURVEY.md §3 "Distributed communication
backend"), never a host round-trip.

Multi-host: under `jax.distributed`, ``make_mesh`` uses all global devices and
``shard_data`` accepts process-local rows via
``jax.make_array_from_process_local_data``.
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P


def make_mesh(
    axis_sizes: Optional[Dict[str, int]] = None,
    *,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Build a Mesh with named axes ("data", "chains") by default.

    axis_sizes: e.g. {"data": 2, "chains": 4}. A single -1 entry is inferred
    from the device count. Default: all devices on the "data" axis.
    """
    if devices is None:
        devices = jax.devices()
    devices = np.asarray(devices)
    n = devices.size
    if axis_sizes is None:
        axis_sizes = {"data": n, "chains": 1}
    sizes = dict(axis_sizes)
    unknown = [k for k, v in sizes.items() if v == -1]
    if len(unknown) > 1:
        raise ValueError("at most one axis size may be -1")
    if unknown:
        known = int(np.prod([v for v in sizes.values() if v != -1]))
        if n % known:
            raise ValueError(f"{n} devices not divisible by {known}")
        sizes[unknown[0]] = n // known
    shape = tuple(sizes.values())
    if int(np.prod(shape)) != n:
        raise ValueError(f"mesh {sizes} needs {np.prod(shape)} devices, have {n}")
    return Mesh(devices.reshape(shape), tuple(sizes.keys()))


def row_partition_specs(data, axis: str = "data", row_axes=None):
    """PartitionSpec pytree putting ``axis`` on each leaf's data-row axis.

    row_axes: per-leaf row-axis pytree (``Model.data_row_axes``); default
    axis 0 everywhere.  A leaf with rows on axis 1 (e.g. a transposed
    ``xT``) gets P(None, axis) so the mesh splits rows, not features.
    A negative row axis means the leaf carries no rows (sentinel/scalar
    markers) and is fully replicated.
    """
    if row_axes is None:
        row_axes = jax.tree.map(lambda _: 0, data)
    return jax.tree.map(
        lambda _, ax: P() if ax < 0 else P(*([None] * ax + [axis])),
        data, row_axes,
    )


def shard_data(data, mesh: Mesh, axis: str = "data", row_axes=None):
    """Place a pytree of arrays with data rows sharded over ``axis``.

    Rows must divide evenly by the axis size (benchmark datasets are sized
    accordingly; use ``truncate_to_multiple`` first otherwise).
    row_axes: see ``row_partition_specs``.

    Rows that arrive as global arrays already laid out this way stay where
    they are (a ``device_put`` to the sharding an array has moves nothing):
    the span ``shard_data`` says how many bytes there are, over how many
    shards, and how many of them had to move (``moved_bytes``: 0 for rows
    born on their chips).
    """
    from .. import telemetry
    from .primitives import placed, predict_tree_bytes, shard_put

    size = mesh.shape[axis]
    if row_axes is None:
        row_axes = jax.tree.map(lambda _: 0, data)
    specs = row_partition_specs(data, axis, row_axes)

    def check(x, ax):
        # shape metadata only: a host array goes from the host straight to
        # its shards, never whole onto one device first
        x = x if hasattr(x, "shape") else jnp.asarray(x)
        if ax >= 0 and x.shape[ax] % size:  # row-less sentinels replicate
            raise ValueError(
                f"rows {x.shape[ax]} not divisible by mesh axis {axis}={size}; "
                "use truncate_to_multiple or pad the dataset"
            )
        return x

    data = jax.tree.map(check, data, row_axes)
    to_move = jax.tree.map(
        lambda x, spec: None if placed(x, mesh, spec) else x, data, specs
    )
    with telemetry.span(
        "shard_data", bytes=predict_tree_bytes(data), shards=int(size),
        moved_bytes=predict_tree_bytes(to_move),
    ):
        return jax.block_until_ready(shard_put(data, mesh, specs))


def truncate_to_multiple(data, k: int):
    """Drop trailing rows so the leading axis divides k."""

    def trunc(x):
        n = (x.shape[0] // k) * k
        return x[:n]

    return jax.tree.map(trunc, data)


def run_over_chains(mesh: Mesh, vrun, *args):
    """shard_map a vmapped chain runner over the mesh "chains" axis and run.

    Every arg must have chains as its leading axis; outputs likewise (the
    P("chains") out_spec is applied as a pytree prefix).  Shared dispatch
    for the samplers that parallelize only over chains (SG-HMC, tempering)
    — re-exported from `primitives`, where it is a `map_shards` +
    `shard_put` composition.
    """
    from .primitives import run_over_chains as _run

    return _run(mesh, vrun, *args)


def process_local_shard(data, mesh: Mesh, axis: str = "data", row_axes=None):
    """Multi-host path: assemble a global sharded array from per-process rows.

    Each process passes only its local rows; jax glues them into one global
    array laid out over ``axis`` (ICI within host, DCN across hosts).
    row_axes: see ``row_partition_specs`` — transformed layouts (e.g. a
    transposed ``xT``) shard their row axis, wherever it lives.
    """
    from .primitives import shard_put

    specs = row_partition_specs(data, axis, row_axes)
    return shard_put(data, mesh, specs, process_local=True)
