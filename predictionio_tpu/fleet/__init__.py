"""Fleet subsystem (ISSUE 10): multi-chip sharded serving from
TPU-resident factor state + a multi-worker distributed training tier.

Three planes:

- **coordinator.py** — N `TrainScheduler` workers cooperating on ONE
  shared-storage job queue via compare-and-set claims (fenced
  claim_token + generation, CAS stale-heartbeat steal), with
  heartbeating worker records and `pio fleet status`,
- **distributed.py** — jax.distributed-style multi-host init config
  (coordinator address, process id/count) with a single-host fallback
  so every test and laptop runs the same code,
- **runtime.py** — `ShardedRuntime`: factor state row-sharded across a
  serving mesh, recommend/similar/fold_in lowered as sharded
  executables (local top-k per shard + global merge), so one model
  serves a catalog larger than a single chip's HBM.

Import discipline: this package sits on server/console control paths —
it must not import jax. `runtime` (which does) loads lazily through
module __getattr__.
"""

from predictionio_tpu.fleet.coordinator import (
    WORKER_ENTITY,
    FleetConfig,
    FleetMember,
    WorkerInfo,
    WorkerRegistry,
    fleet_status,
)
from predictionio_tpu.fleet.distributed import DistributedConfig

_LAZY_RUNTIME = (
    "ShardedRuntime",
    "OversizedModelError",
    "factor_state_bytes",
    "check_single_device_budget",
)

__all__ = [
    "DistributedConfig",
    "FleetConfig",
    "FleetMember",
    "WORKER_ENTITY",
    "WorkerInfo",
    "WorkerRegistry",
    "fleet_status",
    *_LAZY_RUNTIME,
]


def bridge_sharded_metrics(registry):
    """Count the sharded tier's batches into `registry` off the span
    `sharded.dispatch` that `ShardedRuntime.recommend` records (the span
    is the one observation, as with the dispatcher's queue wait):
    `sharded_batches_total{form}` by the exclusion's WIRE form ("none";
    "rows" = shipped as ids, a list of at most ROWLIST_MAX a query;
    "mask" = shipped as packed words, a dense filter or a wider list)
    and `sharded_exclusion_bytes_total`, the bytes of exclusion the host
    shipped in either form. The two families are mounted by the first
    such span — where a `ShardedRuntime` has been built and serves —
    so a server whose engine has no sharded tier shows neither (ISSUE
    37). Returns the callback, for `unbridge`."""
    from predictionio_tpu.obs import spans as _spans

    mounted: list = []  # the two families, once the first span came

    def observe(sp):
        if not mounted:
            mounted.append(registry.counter(
                "sharded_batches_total",
                "batches through ShardedRuntime.recommend, by exclusion "
                "wire form",
                labelnames=("form",),  # label-bound: literal none|rows|mask
            ))
            mounted.append(registry.counter(
                "sharded_exclusion_bytes_total",
                "bytes of exclusion (row lists or packed words) shipped",
            ))
        batches, nbytes = mounted
        batches.inc(form=sp.attrs.get("form", "none"))
        nbytes.inc(float(sp.attrs.get("exclusion_bytes", 0)))

    _spans.get_default_recorder().bridge("sharded.dispatch", observe)
    return observe


__all__.append("bridge_sharded_metrics")


def __getattr__(name):
    if name in _LAZY_RUNTIME:
        from predictionio_tpu.fleet import runtime as _runtime

        return getattr(_runtime, name)
    raise AttributeError(
        f"module {__name__!r} has no attribute {name!r}"
    )
