"""Device mesh construction and canonical shardings.

The framework's parallelism model (SURVEY.md §2.12 mapping):
- **dp** (data axis): interaction edge lists, event batches, eval query
  batches are sharded here. Segment-sums over sharded edges become local
  partial reductions + an ICI all-reduce (GSPMD) — the TPU-native analogue
  of Spark's `aggregateByKey` shuffle (reference PEventAggregator.scala:192).
- **mp** (model axis): large factor/embedding matrices are row-sharded here
  (the analogue of the reference's RDD-backed PAlgorithm models, e.g. ALS
  user/product factor RDDs, PAlgorithm.scala:73-90).

Engines declare how much of each axis they want via `MeshConf` (the
engine.json `mesh` key — the re-design of the reference's `sparkConf`
pass-through, WorkflowUtils.extractSparkConf:316).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXIS = "dp"
MODEL_AXIS = "mp"


def shard_map(fn, mesh, in_specs, out_specs, check: bool = False):
    """`jax.shard_map` with the replication check off by default — every
    sharded program in the tree builds through this one call site."""
    return jax.shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=check,
    )


@dataclass(frozen=True)
class MeshConf:
    """Mesh wiring parsed from an engine variant's `mesh` JSON object.

    `dp`/`mp` of -1 mean "fill with whatever devices remain" (at most one
    axis may be -1). `devices` of 0 means all visible devices.
    """

    dp: int = -1
    mp: int = 1
    devices: int = 0

    @staticmethod
    def from_json(obj: Optional[dict]) -> "MeshConf":
        obj = obj or {}
        return MeshConf(
            dp=int(obj.get("dp", -1)),
            mp=int(obj.get("mp", 1)),
            devices=int(obj.get("devices", 0)),
        )

    def build(self, devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
        devs = list(devices if devices is not None else jax.devices())
        n = self.devices or len(devs)
        if n > len(devs):
            raise ValueError(
                f"mesh config requests {n} devices but only {len(devs)} visible"
            )
        devs = devs[:n]
        dp, mp = self.dp, self.mp
        if dp == -1 and mp == -1:
            raise ValueError("at most one mesh axis may be -1")
        if dp == -1:
            dp = n // mp
        if mp == -1:
            mp = n // dp
        if dp * mp != n:
            raise ValueError(f"mesh {dp}x{mp} does not cover {n} devices")
        return Mesh(np.array(devs).reshape(dp, mp), (DATA_AXIS, MODEL_AXIS))


def make_mesh(
    n_devices: Optional[int] = None,
    mp: Optional[int] = None,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Default mesh: a (dp, mp) grid over the first `n_devices` devices.

    `mp` defaults to 2 when the device count is even (so model-axis sharding
    paths are exercised), else 1. On a single chip this degenerates to a
    1x1 mesh, and every sharded program is trivially valid.
    """
    devs = list(devices if devices is not None else jax.devices())
    n = n_devices or len(devs)
    if n > len(devs):
        raise ValueError(f"requested {n} devices but only {len(devs)} visible")
    if mp is None:
        mp = 2 if n % 2 == 0 and n > 1 else 1
    return MeshConf(dp=-1, mp=mp).build(devs[:n])


def edge_sharding(mesh: Mesh) -> NamedSharding:
    """Sharding for per-edge/per-example arrays: split over the data axis."""
    return NamedSharding(mesh, P(DATA_AXIS))


def factor_sharding(mesh: Mesh) -> NamedSharding:
    """Sharding for (N, K) parameter matrices: rows split over the model
    axis, feature dim replicated."""
    return NamedSharding(mesh, P(MODEL_AXIS, None))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def serving_mesh(
    n_shards: Optional[int] = None,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """A 1-D model-axis mesh for the sharded serving tier (ISSUE 10):
    every device is one factor shard, so a catalog's row-sharded factor
    matrices spread over ALL visible HBM. Train meshes are 2-D (dp×mp)
    because edges and factors shard differently; serving has only
    factor state, so one axis is the whole story."""
    devs = list(devices if devices is not None else jax.devices())
    n = n_shards or len(devs)
    if n > len(devs):
        raise ValueError(
            f"serving mesh requests {n} shards but only {len(devs)} "
            "devices visible"
        )
    return Mesh(np.array(devs[:n]), (MODEL_AXIS,))


def pad_rows_to_shards(n_rows: int, n_shards: int) -> int:
    """Row count padded so every shard owns an equal whole slab."""
    return -(-max(n_rows, 1) // n_shards) * n_shards


def shard_rows(
    mesh: Mesh, array: np.ndarray, axis_name: str = MODEL_AXIS,
    pad_to: int = 0,
):
    """Zero-pad axis 0 to a whole-slab multiple of the axis size (and to
    at least `pad_to` rows) and row-shard it over `axis_name` (remaining
    axes replicated). Callers must keep pad rows inert (zero factors
    score 0 and are masked out of top-k by the global-index pad mask).

    Each shard's slab is cut from the HOST array as it is put: routing
    through jnp.asarray first would materialize the whole matrix on the
    default device before resharding — an instant OOM for exactly the
    over-one-HBM catalogs the sharded tier exists to hold. A slab inside
    the array is a view of it; only a slab that reaches into the pad is
    copied, so the whole table (10 GB at 19.7 M rows of rank 128) never
    is."""
    n = int(mesh.shape[axis_name])
    rows = array.shape[0]
    n_p = pad_rows_to_shards(max(rows, pad_to), n)
    spec = P(axis_name, *([None] * (array.ndim - 1)))

    def slab(index):
        lo, hi, _ = index[0].indices(n_p)
        part = array[min(lo, rows):min(hi, rows)]
        if hi > rows:
            part = np.concatenate([
                part,
                np.zeros((hi - max(lo, rows),) + array.shape[1:], array.dtype),
            ])
        return part

    return jax.make_array_from_callback(
        (n_p,) + array.shape[1:], NamedSharding(mesh, spec), slab
    )


def pad_and_shard_rows(mesh: Mesh, *arrays: np.ndarray):
    """Zero-pad axis 0 of each array to a multiple of the mesh size and
    shard axis 0 over the data axis (remaining axes replicated).

    Callers must ensure zero rows are inert in their reductions (weight-0
    samples, empty indicator rows). All arrays must share axis-0 length.
    Returns jax arrays, one per input."""
    import jax.numpy as jnp

    pad = (-arrays[0].shape[0]) % mesh.devices.size
    out = []
    for a in arrays:
        if pad:
            a = np.concatenate(
                [a, np.zeros((pad,) + a.shape[1:], dtype=a.dtype)]
            )
        spec = P(DATA_AXIS, *([None] * (a.ndim - 1)))
        out.append(jax.device_put(jnp.asarray(a), NamedSharding(mesh, spec)))
    return tuple(out)
