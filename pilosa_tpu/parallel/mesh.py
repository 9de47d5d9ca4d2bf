"""Device mesh construction and shard placement.

The shard axis is the data-parallel axis: shard s of an index maps to
device ``s % n_devices`` by stacking per-shard tiles along axis 0 of a
global array sharded with ``PartitionSpec("shards", ...)``.  This is
the static analog of the reference's jump-hash shard→node snapshot
(disco/snapshot.go:54-69, cluster.go:107-230): placement is a pure
function of (shard count, mesh), with no coordination service.

A second mesh axis ("rows") shards batched row scans (TopK/GroupBy row
blocks) — the closest thing a bitmap database has to model parallelism;
there is no sequence-parallel analog (SURVEY §5.7).
"""

from __future__ import annotations

import numpy as np
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def make_mesh(n_devices: int | None = None, rows: int = 1) -> Mesh:
    """A (rows, shards) mesh over the first rows*shards devices."""
    devs = jax.devices()
    if n_devices is None:
        n_devices = len(devs)
    assert n_devices % rows == 0
    shape = (rows, n_devices // rows)
    return Mesh(np.array(devs[:n_devices]).reshape(shape), ("rows", "shards"))


def shard_spec(batch_axes: int = 0) -> P:
    """PartitionSpec for a (S, ..., W) stack of shard tiles: axis 0 on
    the 'shards' mesh axis, everything else replicated."""
    return P(*( ("shards",) + (None,) * (batch_axes + 1) ))


def place_shards(mesh: Mesh, tiles, batch_axes: int = 0):
    """Put a stacked (S, ..., W) host array onto the mesh, shard axis 0.

    S must be a multiple of the shards axis size (pad with zero tiles —
    zero shards are harmless for every reduction we run).
    """
    tiles = np.asarray(tiles)
    n = mesh.shape["shards"]
    s = tiles.shape[0]
    if s % n:
        pad = n - s % n
        tiles = np.concatenate(
            [tiles, np.zeros((pad,) + tiles.shape[1:], dtype=tiles.dtype)])
    sharding = NamedSharding(mesh, shard_spec(batch_axes))
    return jax.device_put(tiles, sharding)


def shard_map_nocheck(body, mesh: Mesh, in_specs, out_specs):
    """shard_map with replication checking off (the bodies call
    Pallas kernels and reduce their partials with explicit psums)."""
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def flat_spec(ndim: int, shard_axis: int = 0) -> P:
    """PartitionSpec placing `shard_axis` over ALL mesh devices (both
    mesh axes flattened) — the layout shard_map kernel bodies consume:
    every device holds a contiguous slice of the shard axis and runs
    the same per-shard program, partials psum over the whole mesh."""
    return P(*([None] * shard_axis + [("rows", "shards")]
               + [None] * (ndim - shard_axis - 1)))


def place_flat(mesh: Mesh, tiles, shard_axis: int = 0):
    """device_put with `shard_axis` zero-padded to a multiple of the
    TOTAL device count and sharded over all of them (flat_spec).  Used
    by the fused GroupBy kernel paths, where candidate rows replicate
    and the shard axis is the only data-parallel axis."""
    tiles = np.asarray(tiles)
    n = int(mesh.devices.size)
    s = tiles.shape[shard_axis]
    if s % n:
        widths = [(0, 0)] * tiles.ndim
        widths[shard_axis] = (0, n - s % n)
        tiles = np.pad(tiles, widths)
    return jax.device_put(
        tiles, NamedSharding(mesh, flat_spec(tiles.ndim, shard_axis)))
