"""Storage-engine device mesh: the shard_map substrate.

The batched sharded scan families execute over a *named* device mesh:
the stacked-shard pytree (``core.table.stacked_shards``) carries every
shard on one leading axis, and that axis is bound to the mesh's
``"shard"`` axis so each device owns a contiguous slice of shards.
Cross-shard reductions become axis collectives inside the mapped body
(``jax.lax.pmax`` for the hybrid stitch's rho_m, ``psum``/``pmin`` for
the output accounting) -- int32 add/max/min are associative and
commutative, so the collective reductions are bit-identical to the
single-device stacked axis reductions for any device count.

This module owns everything device-shaped so ``core.engine`` never
touches ``jax.local_devices`` directly:

* ``make_scan_mesh``   -- mesh construction (shard axis + optional
  second query-batch axis for 2-D read bursts), cached per process.
* ``stacked_specs`` / ``batch_spec`` -- PartitionSpec prefixes for the
  stacked table/index pytrees and the per-query bound vectors.

The model-parallel mesh for the learned components lives in
``launch.mesh``; this one is deliberately separate -- the storage
engine's shard axis has nothing to do with data/model parallelism.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

SHARD_AXIS = "shard"
QUERY_AXIS = "qbatch"


@functools.lru_cache(maxsize=32)
def make_scan_mesh(n_shards: int, query_axis: int = 1) -> Optional[Mesh]:
    """Mesh binding the stacked-shard axis to local devices.

    Picks the largest device count d >= 2 that divides ``n_shards``
    (each device then owns ``n_shards / d`` consecutive shards of the
    stacked pytree); ``query_axis > 1`` additionally folds a second
    ``"qbatch"`` axis for 2-D read bursts, so d * query_axis devices
    are claimed.  Returns None when no such placement exists -- the
    caller falls back to the single-device stacked dispatch (and
    records the tier; see ``core.engine.ScanEngine``).

    The device set is fixed per process, so the mesh is cached per
    (n_shards, query_axis).
    """
    devices = jax.local_devices()
    q = max(1, int(query_axis))
    avail = len(devices) // q
    d = 0
    for cand in range(min(n_shards, avail), 1, -1):
        if n_shards % cand == 0:
            d = cand
            break
    if d < 2:
        return None
    grid = np.array(devices[: d * q]).reshape(d, q)
    if q == 1:
        return Mesh(grid[:, 0], (SHARD_AXIS,))
    return Mesh(grid, (SHARD_AXIS, QUERY_AXIS))


def stacked_specs() -> P:
    """PartitionSpec *prefix* for any stacked-shard pytree.

    Every leaf of ``StackedShards`` (column planes ``(S, pages, psz,
    ...)``, ``shard_ids``/``local_pages``/``n_rows`` ``(S,)``) and of a
    stacked ``AdHocIndex`` carries the shard axis in front, so one
    leading-axis spec broadcast over the pytree shards them all.
    """
    return P(SHARD_AXIS)


def batch_spec(mesh: Mesh) -> P:
    """Spec for per-query ``(B,)`` operands and results: split over the
    query-batch axis on 2-D meshes, replicated on 1-D meshes."""
    return P(QUERY_AXIS) if QUERY_AXIS in mesh.axis_names else P()


def replicated_spec() -> P:
    return P()


def query_axis_size(mesh: Mesh) -> int:
    return int(mesh.shape.get(QUERY_AXIS, 1))


def mesh_device_count(mesh: Mesh) -> int:
    return int(np.prod(list(mesh.shape.values())))
