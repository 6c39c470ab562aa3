"""Sharded composite event store — horizontal scale-out across N stores.

The reference's at-scale event store is HBase: events distributed over
region servers by row key (entity-first key design, HBEventsUtil.scala:
47-106), scanned in parallel per region (HBPEvents.scala:84-90). This
backend plays that role with N underlying stores (typically `remote`
storage daemons on separate hosts): every event lives on exactly ONE
shard, chosen by the same crc32 entity hash the partitioned-read API
uses (base.shard_of) — so entity locality holds (all of one entity's
events are on one shard, like one HBase row-key prefix in one region),
ingest load and storage volume split ~evenly, and a training read with
`EventQuery.shard=(i, N)` goes STRAIGHT to shard i with no cross-shard
traffic at all: N parallel readers each stream from their own daemon,
which is the HBase parallel-region-scan picture end to end.

Configure:

  PIO_STORAGE_SOURCES_<NAME>_TYPE=sharded
  PIO_STORAGE_SOURCES_<NAME>_SHARDS=host1:port1,host2:port2,...
  PIO_STORAGE_SOURCES_<NAME>_ALLOW_PARTIAL=1   # optional, see below
  PIO_STORAGE_SOURCES_<NAME>_RETRIES=2         # optional
  PIO_STORAGE_SOURCES_<NAME>_REPLICAS=2        # optional, see below

Metadata/model repositories are NOT sharded — point them at a single
source (the reference likewise kept metadata in one store while events
scaled out over HBase).

Failure contract (the HBase-availability role, StorageClient.scala:37-46
retry tuning + Storage.scala:335 verifyAllDataObjects):

- Every child call is retried ``RETRIES`` times with exponential backoff
  before the shard is declared down — transient daemon hiccups (restart,
  dropped keep-alive) self-heal invisibly.
- After retries, the call raises :class:`ShardDownError` naming the
  shard index and address — failures are loud and attributable, never a
  bare connection error from somewhere inside a merge.
- ``ALLOW_PARTIAL=1`` opts broadcast READS (un-sharded find, get,
  aggregate_properties) into degraded mode: a down shard is skipped, a
  warning is logged, and the affected shard indices are recorded on
  ``last_degraded_shards`` for the caller to surface. Stats-grade reads
  keep working through a partial outage; training reads should leave it
  off (a silent hole in training data is worse than an error). WRITES
  are never partial: an unreachable home shard always raises.
- ``health()`` pings every shard and reports per-shard status — wired
  into ``pio status`` (tools/console.py) the way the reference's deep
  storage check verifies every data object.
- ``REPLICAS=R`` (default 1) writes every event to its home shard AND
  the next R-1 shards (successor replication, the HBase-region-replica
  role). Reads then survive a down shard COMPLETELY: an entity- or
  partition-scoped stream fails over to the successor, and the
  broadcast merge reads each shard primary-only (``shard=(i, N)``
  filters server-side — replica copies on successors have a different
  entity hash and are filtered out) with per-partition failover.
  Write durability contract: the write succeeds when the PRIMARY
  commits; replica copy failures degrade redundancy and are logged
  loudly but do not fail the write (no hinted handoff — a down shard's
  replicas catch up only via re-import).
- ``HEDGED_READS`` (default on when REPLICAS > 1) hedges idempotent
  entity reads (`find_entities_batch`) to the copy holder after a
  p95-derived delay — first answer wins
  (``storage_hedged_reads_total{outcome}``). Because replica copies
  are best-effort, a winning hedge can reflect a slightly-shorter
  history than the slow home shard held; set ``HEDGED_READS=0`` where
  that bounded staleness is not acceptable.
"""

from __future__ import annotations

import dataclasses
import heapq
import itertools
import logging
import time
import threading
from concurrent.futures import (
    ThreadPoolExecutor,
    TimeoutError as FuturesTimeout,
    as_completed,
)
from typing import Any, Callable, Iterator, Optional, Sequence

from predictionio_tpu.data.event import Event
from predictionio_tpu.data.storage import base
from predictionio_tpu.data.storage.base import (
    EventQuery,
    StorageError,
    StorageUnreachableError,
    shard_of,
)

# the only failure classes retried/attributed as "shard down": daemon
# connectivity (StorageUnreachableError from the remote client, raw
# OSError from direct-composed stores). Application-level StorageErrors
# (auth rejected, malformed query, server bug) propagate untouched —
# deterministic, not an outage, and backoff would just add latency.
_TRANSIENT = (StorageUnreachableError, OSError)

log = logging.getLogger(__name__)


class PartialBatchWriteError(StorageError):
    """A bulk write landed on some shards but not others.

    `ids` aligns with the input positions: the assigned event_id where
    the write persisted, None where its shard failed. Callers that
    report per-event statuses (the event server's batch endpoint) can
    stay accurate instead of declaring the whole batch failed — a
    blanket failure invites a client retry that duplicates the events
    that DID persist."""

    def __init__(self, ids, cause: Exception):
        n_fail = sum(1 for i in ids if i is None)
        super().__init__(
            f"bulk write failed on {n_fail}/{len(ids)} events: {cause}"
        )
        self.ids = list(ids)
        self.cause = cause


class ShardDownError(StorageError):
    """A shard stayed unreachable through the retry budget.

    Carries the shard identity so operators (and degraded-read callers)
    know exactly which daemon to look at."""

    def __init__(self, shard_index: int, address: str, cause: Exception):
        super().__init__(
            f"shard {shard_index} ({address}) is down: {cause}"
        )
        self.shard_index = shard_index
        self.address = address
        self.cause = cause


class ShardedEventStore(base.EventStore):
    """Entity-hash composite over N child event stores."""

    #: retry schedule base — attempt i sleeps BACKOFF_BASE * 2**i
    BACKOFF_BASE = 0.05

    #: hedged-read tuning (ISSUE 10 satellite): the hedge fires when the
    #: primary is still in flight past the recent read-latency p95
    #: (bounded window, conservative cold-start default, floor so a
    #: microsecond p95 on embedded stores doesn't duplicate every read)
    HEDGE_WINDOW = 512
    HEDGE_DEFAULT_DELAY_S = 0.05
    HEDGE_MIN_DELAY_S = 0.002

    def __init__(
        self,
        config: Optional[dict] = None,
        stores: Optional[Sequence[base.EventStore]] = None,
        allow_partial: Optional[bool] = None,
        retries: Optional[int] = None,
    ):
        config = config or {}
        if stores is not None:  # direct composition (tests, embedding)
            self._stores = list(stores)
        else:
            spec = config.get("SHARDS", "")
            addrs = [a.strip() for a in spec.split(",") if a.strip()]
            if not addrs:
                raise StorageError(
                    "sharded backend needs SHARDS=host:port[,host:port...]"
                )
            from predictionio_tpu.data.storage.remote import RemoteEventStore

            # child config inherits everything except SHARDS (AUTH_KEY,
            # TIMEOUT, … — non-localhost daemons REQUIRE --auth-key)
            child_cfg = {
                k: v
                for k, v in config.items()
                if k not in (
                    "SHARDS", "ALLOW_PARTIAL", "RETRIES", "REPLICAS"
                )
            }
            self._stores = []
            for addr in addrs:
                host, _, port = addr.rpartition(":")
                self._stores.append(
                    RemoteEventStore(
                        dict(child_cfg, HOST=host or "127.0.0.1", PORT=port)
                    )
                )
        if not self._stores:
            raise StorageError("sharded backend needs at least one shard")
        self.allow_partial = (
            allow_partial
            if allow_partial is not None
            else str(config.get("ALLOW_PARTIAL", "")).strip()
            in ("1", "true", "yes")
        )
        self.retries = (
            int(retries)
            if retries is not None
            else int(config.get("RETRIES", "2"))
        )
        self.replicas = max(
            1, min(int(config.get("REPLICAS", "1")), len(self._stores))
        )
        # hedged reads (ISSUE 10 satellite): ON by default when replica
        # copies exist — an idempotent read stuck past the p95 fires a
        # duplicate against the next copy holder, first answer wins
        self.hedged_reads = self.replicas > 1 and str(
            config.get("HEDGED_READS", "1")
        ).strip() not in ("0", "false", "no")
        self._read_lat: list[float] = []
        self._lat_lock = threading.Lock()
        from predictionio_tpu.obs import get_default_registry

        self._hedge_counter = get_default_registry().counter(
            "storage_hedged_reads_total",
            "hedged idempotent replica reads by outcome",
            ("outcome",),  # label-bound: literal outcome set
        )
        #: shard indices skipped by the most recent degraded broadcast
        #: read (empty when that read was complete). Best-effort operator
        #: diagnostic: updated only by broadcast reads, unsynchronized
        #: across concurrent readers — inspect right after the read whose
        #: completeness you care about, never for correctness decisions.
        self.last_degraded_shards: list[int] = []
        # broadcasts fan out concurrently: one wall-clock round trip for
        # N shards instead of N sequential ones (ADVICE r4: explicit-id
        # eviction was O(N) round trips per insert). Sized for several
        # CONCURRENT callers (the event server's writer threads), not
        # one: at exactly n_stores workers, 8 ingest writers funnel
        # their per-shard bulk writes through n_stores threads and the
        # composite throttles BELOW a single store (seen in ISSUE 13).
        self._pool = ThreadPoolExecutor(
            max_workers=max(8, 4 * len(self._stores)),
            thread_name_prefix="shardcast",
        )
        # embedded (in-process) children share the caller's GIL: pool
        # fan-out for their CPU-bound writes buys nothing and the hop
        # costs more than a small write — those run inline. Children
        # that declare IO_PARALLEL_WRITES (remote daemons, postgres)
        # release the GIL on the network/DB wait, so fan-out is a
        # genuine wall-clock win for them at any batch size.
        self._all_local_children = not any(
            getattr(s, "IO_PARALLEL_WRITES", False) for s in self._stores
        )
        # hedged primaries/hedges run on their OWN pool: _hedged_call
        # executes inside _broadcast's pool tasks, and submitting the
        # duplicate reads back into a saturated shardcast pool would
        # deadlock (every worker waiting on a future no worker can run)
        self._hedge_pool = ThreadPoolExecutor(
            max_workers=max(4, 2 * len(self._stores)),
            thread_name_prefix="shardhedge",
        )

    @property
    def n_shards(self) -> int:
        return len(self._stores)

    def shard_address(self, sx: int) -> str:
        """Human-readable identity of shard `sx` for errors/health."""
        s = self._stores[sx]
        client = getattr(s, "_client", None)
        if client is not None and hasattr(client, "host"):
            return f"{client.host}:{client.port}"
        return f"local[{sx}]:{type(s).__name__}"

    def _for_entity(self, entity_id: str) -> int:
        return shard_of(entity_id, self.n_shards)

    # -- hedged reads (ISSUE 10 satellite; PR-4 resilience follow-up) ------
    def _record_read_latency(self, seconds: float) -> None:
        with self._lat_lock:
            self._read_lat.append(seconds)
            if len(self._read_lat) > self.HEDGE_WINDOW:
                del self._read_lat[: -self.HEDGE_WINDOW]

    def hedge_delay_s(self) -> float:
        """The p95-derived hedge trigger: a read still in flight past
        the recent p95 is probably stuck behind a slow/struggling shard
        — that is the moment the duplicate fires. Cold start (no
        history) uses a conservative default so the hedge never beats a
        normal-latency answer."""
        with self._lat_lock:
            lat = list(self._read_lat)
        if len(lat) < 20:
            return self.HEDGE_DEFAULT_DELAY_S
        lat.sort()
        p95 = lat[min(len(lat) - 1, int(0.95 * len(lat)))]
        return max(self.HEDGE_MIN_DELAY_S, p95)

    def _hedged_call(self, chain: Sequence[int], make_call):
        """Run an IDEMPOTENT read against `chain[0]`, hedging to the
        next replica after the p95-derived delay — first answer wins,
        the loser is abandoned (its future still drains in the pool).
        Only replica-holding chains hedge; a single-copy read falls
        back to the plain retry path. `make_call(sx)` must return a
        zero-arg callable running the read against shard sx.

        Counter: storage_hedged_reads_total{outcome} —
          primary_fast  primary answered before the hedge delay
          primary       hedge fired, primary still answered first
          hedge         the hedge's answer won
          failover      primary raised and the hedge rescued the read
        """
        def serial(shards: Sequence[int]):
            last: Optional[ShardDownError] = None
            for sx in shards:
                try:
                    t0 = time.monotonic()
                    out = self._shard_call(sx, make_call(sx))
                    self._record_read_latency(time.monotonic() - t0)
                    return out
                except ShardDownError as e:
                    last = e
                    log.warning(
                        "shard %d down for read; trying replica", sx
                    )
            raise last  # type: ignore[misc]

        if len(chain) < 2 or not self.hedged_reads:
            return serial(chain)
        t0 = time.monotonic()
        primary = self._hedge_pool.submit(
            self._shard_call, chain[0], make_call(chain[0])
        )
        try:
            out = primary.result(timeout=self.hedge_delay_s())
            self._record_read_latency(time.monotonic() - t0)
            self._hedge_counter.inc(outcome="primary_fast")
            return out
        except FuturesTimeout:
            pass
        except ShardDownError:
            # primary died before the hedge even fired: serial failover
            # over the remaining chain (counted as failover either way)
            self._hedge_counter.inc(outcome="failover")
            return serial(chain[1:])
        hedge = self._hedge_pool.submit(
            self._shard_call, chain[1], make_call(chain[1])
        )
        errors: list[Exception] = []
        for f in as_completed([primary, hedge]):
            try:
                out = f.result()
            except Exception as e:
                errors.append(e)
                continue
            self._record_read_latency(time.monotonic() - t0)
            if f is primary:
                outcome = "primary"
            else:
                outcome = "hedge" if not errors else "failover"
            self._hedge_counter.inc(outcome=outcome)
            return out
        # both copies failed; deeper replicas (if any) serially
        if len(chain) > 2:
            self._hedge_counter.inc(outcome="failover")
            return serial(chain[2:])
        raise errors[0]

    def _replica_chain(self, home: int) -> list[int]:
        """Home shard first, then its R-1 successors (copy holders)."""
        return [
            (home + k) % self.n_shards for k in range(self.replicas)
        ]

    # -- retry / failure core ---------------------------------------------
    def _shard_call(
        self, sx: int, fn: Callable, *args, retries: Optional[int] = None
    ):
        """Run one child-store call, retrying CONNECTIVITY failures with
        backoff; after the budget, raise ShardDownError naming the shard.
        Application-level StorageErrors pass through untouched (see
        _TRANSIENT). `retries=0` disables re-invocation for calls that
        are not safe to re-issue (insert: a second invocation mints a
        fresh RPC req_id, defeating the daemon's dedupe and duplicating
        the event — the remote client's own same-req-id retry already
        covers response loss)."""
        budget = self.retries if retries is None else retries
        last: Optional[Exception] = None
        for attempt in range(budget + 1):
            try:
                return fn(*args)
            except _TRANSIENT as e:
                last = e
                if attempt < budget:
                    time.sleep(self.BACKOFF_BASE * (2**attempt))
        raise ShardDownError(sx, self.shard_address(sx), last)  # type: ignore[arg-type]

    def _broadcast(
        self,
        calls: Sequence[tuple[int, Callable, tuple]],
        partial_ok: bool = False,
        retries: Optional[int] = None,
    ) -> dict[int, Any]:
        """Run (shard, fn, args) calls concurrently; returns {shard:
        result}. With partial_ok (and allow_partial on), down shards are
        skipped, logged, and recorded on last_degraded_shards; otherwise
        the first ShardDownError propagates (after ALL calls finish, so
        no child is left mid-flight)."""
        futs = {
            sx: self._pool.submit(
                self._shard_call, sx, fn, *args, retries=retries
            )
            for sx, fn, args in calls
        }
        out: dict[int, Any] = {}
        degraded: list[int] = []
        first_err: Optional[Exception] = None
        for sx, f in futs.items():
            try:
                out[sx] = f.result()
            except ShardDownError as e:
                if partial_ok and self.allow_partial:
                    degraded.append(sx)
                    log.warning("degraded read: skipping %s", e)
                elif first_err is None:
                    first_err = e
            except Exception as e:  # app-level error: still drain the rest
                # (raising mid-loop would abandon in-flight writes — the
                # caller could retry or close() against live futures)
                if first_err is None:
                    first_err = e
        if first_err is not None:
            raise first_err
        if partial_ok:
            self.last_degraded_shards = degraded
        return out

    # -- lifecycle ---------------------------------------------------------
    def init_app(self, app_id: int, channel_id: Optional[int] = None) -> bool:
        res = self._broadcast(
            [
                (sx, s.init_app, (app_id, channel_id))
                for sx, s in enumerate(self._stores)
            ]
        )
        return all(res.values())

    def remove_app(self, app_id: int, channel_id: Optional[int] = None) -> bool:
        res = self._broadcast(
            [
                (sx, s.remove_app, (app_id, channel_id))
                for sx, s in enumerate(self._stores)
            ]
        )
        return all(res.values())

    def close(self) -> None:
        for s in self._stores:
            s.close()
        self._pool.shutdown(wait=False)
        self._hedge_pool.shutdown(wait=False)

    # -- health ------------------------------------------------------------
    def health(self) -> list[dict]:
        """Ping every shard; [{shard, address, alive, error}] per shard.

        One concurrent round — the `pio status` deep check surface
        (reference: Storage.verifyAllDataObjects, Storage.scala:335)."""

        def probe(sx: int, s: base.EventStore):
            client = getattr(s, "_client", None)
            try:
                if client is not None and hasattr(client, "ping"):
                    alive = bool(client.ping())
                    return {"alive": alive, "error": None if alive else "ping failed"}
                # no transport = in-process child: alive by construction
                # (any data-level probe would have side effects — e.g.
                # data_signature(0) creates app-0 tables on SQL stores)
                return {"alive": True, "error": None}
            except Exception as e:  # health never raises
                return {"alive": False, "error": str(e)}

        futs = {
            sx: self._pool.submit(probe, sx, s)
            for sx, s in enumerate(self._stores)
        }
        return [
            {
                "shard": sx,
                "address": self.shard_address(sx),
                **futs[sx].result(),
            }
            for sx in range(self.n_shards)
        ]

    # -- insert-revision tailing (ISSUE 9) ---------------------------------
    def revision_streams(self):
        """One tail stream per shard, each filtered server-side to the
        shard's PRIMARY copies (`shard=(sx, N)` — successor-replica
        copies have a foreign entity hash and are excluded), so a
        consumer folding all streams sees every event exactly once even
        with REPLICAS > 1. Revisions are per-shard monotonic; the
        consumer's durable cursor keeps one entry per stream key."""
        return [
            (f"shard{sx}", s, (sx, self.n_shards))
            for sx, s in enumerate(self._stores)
        ]

    def find_since(
        self,
        app_id: int,
        after_revision: int,
        channel_id: Optional[int] = None,
        limit: Optional[int] = None,
        shard: Optional[tuple[int, int]] = None,
    ):
        raise StorageError(
            "sharded stores have no single revision sequence; tail the "
            "per-shard streams from revision_streams() instead"
        )

    # -- writes: routed by entity hash ------------------------------------
    def insert(
        self, event: Event, app_id: int, channel_id: Optional[int] = None
    ) -> str:
        home = self._for_entity(event.entity_id)
        chain = self._replica_chain(home)
        if event.event_id:
            # explicit-id insert (import/replay/overwrite): the id may
            # already live on a DIFFERENT shard if the entity changed —
            # evict it there or get/delete-by-id would see two copies.
            # Evictions fan out concurrently with the home insert's
            # prerequisite ordering relaxed to: evict first (all shards in
            # one wall-clock round), then insert — ~2 round trips total
            # instead of N sequential (ADVICE r4). Replica holders ARE
            # evicted too: they receive the fresh copy right after, and
            # if that copy write fails the id must be ABSENT there, not
            # stale — a stale copy's entity hash matches a primary
            # partition and would pass the primary-only read filters.
            self._broadcast(
                [
                    (sx, s.delete, (event.event_id, app_id, channel_id))
                    for sx, s in enumerate(self._stores)
                    if sx != home
                ]
            )
        eid = self._shard_call(
            home, self._stores[home].insert, event, app_id, channel_id,
            retries=0,
        )
        if self.replicas > 1:
            self._replicate(
                [(event.with_id(eid), home)], app_id, channel_id
            )
        return eid

    def insert_with_req_id(
        self, event: Event, app_id: int, channel_id: Optional[int],
        req_id: str,
    ) -> str:
        """Caller-stable req_id insert for the event-WAL replayer: routed
        to the home shard's own req-id-deduped insert when the child
        supports it (remote daemons do), so a replay re-send after a
        crash cannot duplicate the row on the shard either. Children
        without the capability fall back to plain insert — the WAL's ack
        file remains the only dedupe there."""
        home = self._for_entity(event.entity_id)
        child = self._stores[home]
        fn = getattr(child, "insert_with_req_id", None)
        if fn is None:
            return self.insert(event, app_id, channel_id)
        eid = self._shard_call(
            home, fn, event, app_id, channel_id, req_id, retries=0,
        )
        if self.replicas > 1:
            self._replicate(
                [(event.with_id(eid), home)], app_id, channel_id
            )
        return eid

    def _replicate(
        self,
        primaries: Sequence[tuple[Event, int]],  # (event WITH id, home)
        app_id: int,
        channel_id: Optional[int],
    ) -> None:
        """Copy committed primaries to their successor shards. Failures
        degrade redundancy, loudly, without failing the write."""
        if self.replicas <= 1 or not primaries:
            return
        per_follower: dict[int, list[Event]] = {}
        for e, home in primaries:
            for sx in self._replica_chain(home)[1:]:
                per_follower.setdefault(sx, []).append(e)
        futs = {
            sx: self._pool.submit(
                self._shard_call, sx, self._stores[sx].insert_batch,
                evs, app_id, channel_id, retries=0,
            )
            for sx, evs in per_follower.items()
        }
        for sx, f in futs.items():
            try:
                f.result()
            except Exception as e:
                log.error(
                    "replica write to shard %d failed — %d event(s) "
                    "have reduced redundancy: %s",
                    sx, len(per_follower[sx]), e,
                )

    def insert_batch(
        self, events: Sequence[Event], app_id: int, channel_id: Optional[int] = None
    ) -> list[str]:
        return self._insert_batch_impl(events, app_id, channel_id, None)

    def insert_batch_with_req_id(
        self, events: Sequence[Event], app_id: int,
        channel_id: Optional[int], req_id: str,
    ) -> list[str]:
        """Bulk insert under ONE caller-stable request id (ISSUE 13
        satellite — the WAL batch-replay seam the sharded store lacked):
        the batch routes to its owning shard groups as usual, and each
        group lands under the DERIVED id ``{req_id}/s{shard}``. Grouping
        is deterministic given the batch (entity hash), so a replay
        re-send after a crash re-forms the same groups under the same
        ids and each remote child's req-id dedupe replays its recorded
        outcome — per-shard exactly-once without N per-event RPCs.
        Children without the capability fall back to plain bulk insert
        (spill-time event-id stamping makes a residual re-insert an
        overwrite, not a duplicate)."""
        return self._insert_batch_impl(events, app_id, channel_id, req_id)

    def _insert_batch_impl(
        self, events: Sequence[Event], app_id: int,
        channel_id: Optional[int], req_id: Optional[str],
    ) -> list[str]:
        if not events:
            return []
        # group per shard so each child gets ONE bulk write, then restore
        # input order for the returned ids (the batch API's per-event
        # status contract depends on positions)
        groups: dict[int, list[tuple[int, Event]]] = {}
        explicit: list[tuple[int, str]] = []  # (home shard, event_id)
        for pos, e in enumerate(events):
            sx = self._for_entity(e.entity_id)
            groups.setdefault(sx, []).append((pos, e))
            if e.event_id:
                explicit.append((sx, e.event_id))
        # explicit-id replays: evict each id from every NON-home shard
        # (replica holders included — see insert()), one bulk delete per
        # shard, all concurrent
        evict_calls = []
        for sx in range(self.n_shards):
            ids = [eid for home, eid in explicit if home != sx]
            if ids:
                evict_calls.append(
                    (sx, self._stores[sx].delete_batch, (ids, app_id, channel_id))
                )
        if evict_calls:
            self._broadcast(evict_calls)
        # per-shard writes fan out concurrently; outcomes are collected
        # per shard so a partial failure stays attributable per EVENT.
        # The LAST group runs inline on the caller thread: with one
        # group (the common small-batch case) the pool round trip
        # disappears entirely, and with several the caller contributes a
        # worker instead of idling on futures.
        def plan(sx: int):
            child = self._stores[sx]
            evs = [e for _p, e in groups[sx]]
            batch_fn = (
                getattr(child, "insert_batch_with_req_id", None)
                if req_id is not None
                else None
            )
            if batch_fn is not None:
                return batch_fn, (evs, app_id, channel_id, f"{req_id}/s{sx}")
            return child.insert_batch, (evs, app_id, channel_id)

        class _Done:
            def __init__(self, value=None, err=None):
                self._value, self._err = value, err

            def result(self):
                if self._err is not None:
                    raise self._err
                return self._value

        def run_inline(sx: int) -> _Done:
            batch_fn, args = plan(sx)
            try:
                return _Done(
                    self._shard_call(sx, batch_fn, *args, retries=0)
                )
            except Exception as e:  # collected like any shard failure
                return _Done(err=e)

        order = list(groups)
        futs: dict[int, Any] = {}
        if self._all_local_children and len(events) < 256:
            # small batches into EMBEDDED children (no remote RPC to
            # overlap): a pool round trip per group costs more than the
            # write itself — run every group on the caller thread
            for sx in order:
                futs[sx] = run_inline(sx)
        else:
            for sx in order[:-1]:
                batch_fn, args = plan(sx)
                futs[sx] = self._pool.submit(
                    self._shard_call, sx, batch_fn, *args,
                    retries=0,  # re-invoking mints fresh req_ids
                )
            futs[order[-1]] = run_inline(order[-1])
        out: list[Optional[str]] = [None] * len(events)
        committed: list[tuple[Event, int]] = []
        # only stamp ids onto event copies when a replica write will
        # consume them — with REPLICAS=1 the per-event with_id() replace
        # (validation and all) was half the sharded batch-insert time
        stamp = self.replicas > 1
        first_err: Optional[Exception] = None
        for sx, pairs in groups.items():
            try:
                ids = futs[sx].result()
            except Exception as e:
                if first_err is None:
                    first_err = e
                continue
            for (pos, e), eid in zip(pairs, ids):
                out[pos] = eid
                if stamp:
                    committed.append((e.with_id(eid), sx))
        self._replicate(committed, app_id, channel_id)
        if first_err is not None:
            raise PartialBatchWriteError(out, first_err)
        return out  # type: ignore[return-value]

    # -- by-id ops: the id does not encode the shard → broadcast -----------
    def get(
        self, event_id: str, app_id: int, channel_id: Optional[int] = None
    ) -> Optional[Event]:
        futs = {
            self._pool.submit(
                self._shard_call, sx, s.get, event_id, app_id, channel_id
            ): sx
            for sx, s in enumerate(self._stores)
        }
        first_err: Optional[ShardDownError] = None
        degraded: list[int] = []
        try:
            for f in as_completed(futs):
                try:
                    e = f.result()
                except ShardDownError as err:
                    degraded.append(futs[f])
                    if first_err is None:
                        first_err = err
                    continue
                if e is not None:
                    # a hit is definitive even if another shard is down:
                    # replica copies are evicted-before-rewrite on
                    # overwrites, so every live copy of an id carries the
                    # same content — return immediately rather than
                    # waiting out a dead shard's retry budget
                    return e
        finally:
            for f in futs:
                f.cancel()
        if first_err is not None and not self.allow_partial:
            # absence is only provable when every shard answered
            raise first_err
        if first_err is not None:
            self.last_degraded_shards = degraded
            log.warning("degraded get(%s): %s", event_id, first_err)
        return None

    def delete(
        self, event_id: str, app_id: int, channel_id: Optional[int] = None
    ) -> bool:
        res = self._broadcast(
            [
                (sx, s.delete, (event_id, app_id, channel_id))
                for sx, s in enumerate(self._stores)
            ]
        )
        return any(res.values())

    def delete_batch(
        self,
        event_ids: Sequence[str],
        app_id: int,
        channel_id: Optional[int] = None,
    ) -> int:
        # one bulk call per child (ids don't encode shards; a miss on one
        # child is a no-op there) instead of K ids × N shards single RPCs
        # — SelfCleaningDataSource deletes expired events in bulk.
        # NOTE with REPLICAS > 1 the return counts removed COPIES (an
        # event deleted from home + follower counts twice); attributing
        # per-event existence would cost a per-id home lookup round.
        ids = list(event_ids)
        res = self._broadcast(
            [
                (sx, s.delete_batch, (ids, app_id, channel_id))
                for sx, s in enumerate(self._stores)
            ]
        )
        return sum(res.values())

    # -- reads -------------------------------------------------------------
    def _guarded_stream(
        self, sx: int, query: EventQuery, partial_ok: bool = False
    ) -> Iterator[Event]:
        """Stream one shard's find(), attributing connectivity failures
        to the shard. Start-of-stream failures (daemon down when the
        scan begins) retry with backoff on a fresh iterator — nothing
        has been yielded yet, so a replay is safe. Mid-stream failures
        (daemon died during the scan) cannot retry without duplicating
        already-yielded events, so they convert straight to the
        attributed error. Only broadcast reads (partial_ok) degrade
        under allow_partial: an entity- or shard-scoped find targets ONE
        shard, and an empty answer there would silently impersonate
        'entity has no events'."""

        def down(e: Exception) -> Optional[ShardDownError]:
            err = ShardDownError(sx, self.shard_address(sx), e)
            if partial_ok and self.allow_partial:
                if sx not in self.last_degraded_shards:
                    self.last_degraded_shards.append(sx)
                log.warning("degraded read: %s", err)
                return None
            return err

        first: Optional[Event] = None
        it: Optional[Iterator[Event]] = None
        for attempt in range(self.retries + 1):
            try:
                it = iter(self._stores[sx].find(query))
                first = next(it)
                break
            except StopIteration:
                return
            except _TRANSIENT as e:
                if attempt < self.retries:
                    time.sleep(self.BACKOFF_BASE * (2**attempt))
                    continue
                err = down(e)
                if err is None:
                    return
                raise err from e
        yield first  # type: ignore[misc]
        try:
            yield from it  # type: ignore[misc]
        except _TRANSIENT as e:
            err = down(e)
            if err is not None:
                raise err from e

    def _failover_stream(
        self,
        chain: Sequence[int],
        query: EventQuery,
        partial_ok: bool = False,
    ) -> Iterator[Event]:
        """Stream `query` from the first LIVE shard in `chain` (home
        first, then its replica holders — each holds the same data for
        this query's scope). Failover happens only before the first
        yield; a mid-stream cut cannot resume on a replica without
        duplicating already-yielded events, so it propagates (or
        degrades under allow_partial for broadcast reads)."""
        last: Optional[ShardDownError] = None
        for j, sx in enumerate(chain):
            yielded = False
            try:
                for e in self._guarded_stream(sx, query):
                    yielded = True
                    yield e
                return
            except ShardDownError as err:
                if yielded:
                    # mid-stream: a replica cannot resume without
                    # duplicating already-yielded events — truncate
                    # (degraded) for broadcast reads, else propagate
                    if partial_ok and self.allow_partial:
                        if chain[0] not in self.last_degraded_shards:
                            self.last_degraded_shards.append(chain[0])
                        log.warning(
                            "degraded read: stream cut mid-flight; %s",
                            err,
                        )
                        return
                    raise
                last = err
                if j + 1 < len(chain):
                    log.warning(
                        "shard %d down; reading partition from replica "
                        "on shard %d", sx, chain[j + 1],
                    )
        if last is not None:
            if partial_ok and self.allow_partial:
                if chain[0] not in self.last_degraded_shards:
                    self.last_degraded_shards.append(chain[0])
                log.warning("degraded read: %s", last)
                return
            raise last

    def find(self, query: EventQuery) -> Iterator[Event]:
        if query.entity_id is not None:
            # entity locality: one shard (plus its replicas) holds
            # everything for this entity — never partial, but with
            # REPLICAS > 1 a down home fails over to a copy holder
            sx = self._for_entity(query.entity_id)
            return self._failover_stream(self._replica_chain(sx), query)
        if (
            query.shard is not None
            and query.shard[1] == self.n_shards
            and 0 <= query.shard[0] < self.n_shards
        ):
            # the partitioned-read contract uses the SAME hash — shard i
            # of N lives entirely on child i: a direct single-daemon
            # stream, the zero-crosstalk HBase parallel-scan case (the
            # child still applies the filter, which also selects EXACTLY
            # partition i's events out of a replica holder on failover)
            return self._failover_stream(
                self._replica_chain(query.shard[0]), query
            )
        self.last_degraded_shards = []
        if self.replicas > 1:
            # replicas would appear R times in a naive merge — read each
            # shard PRIMARY-ONLY (shard=(i, N) filters server-side;
            # copies on successors have a different entity hash) with
            # per-partition failover. A caller-supplied non-aligned
            # (j, m) shard filter is applied client-side on top.
            caller_shard = query.shard

            def partition(i: int) -> Iterator[Event]:
                # limit pushes down per child (the in-order merge takes
                # the global top-`limit` from per-child top-`limit`s)
                # UNLESS a client-side shard re-filter will discard rows
                q_i = dataclasses.replace(
                    query,
                    shard=(i, self.n_shards),
                    limit=None if caller_shard is not None else query.limit,
                )
                stream = self._failover_stream(
                    self._replica_chain(i), q_i, partial_ok=True
                )
                if caller_shard is None:
                    return stream
                j, m = caller_shard
                return (
                    e for e in stream if shard_of(e.entity_id, m) == j
                )

            streams = [partition(i) for i in range(self.n_shards)]
        else:
            streams = [
                self._guarded_stream(sx, query, partial_ok=True)
                for sx in range(self.n_shards)
            ]
        merged = heapq.merge(
            *streams,
            key=lambda e: (e.event_time, e.event_id or ""),
            reverse=query.reversed,
        )
        if query.limit is not None and query.limit >= 0:
            return itertools.islice(merged, query.limit)
        return merged

    def find_entities_batch(
        self,
        app_id,
        entity_type,
        entity_ids,
        channel_id=None,
        event_names=None,
        limit_per_entity=None,
        reversed=True,
    ):
        """Entity locality makes this a per-shard fan-out: each shard
        answers for ITS entities in one bulk call, all shards in one
        concurrent round (never partial — a missing user history would
        silently impersonate a cold-start user; with REPLICAS > 1 a
        down home shard's whole group fails over to the copy holder).

        This is the serving tier's hottest idempotent read (user-history
        exclusion masks), so with replicas it rides the HEDGED path
        (ISSUE 10 satellite): a home-shard read stuck past the p95
        fires the same read at the copy holder and the first answer
        wins — one slow or GC-pausing daemon stops defining the serving
        tail.

        Consistency trade: replica copies are best-effort by the write
        contract (a logged copy failure leaves the successor PARTIAL),
        so a hedge that wins while the home shard is merely slow can
        return a slightly-shorter history than the home would have —
        bounded staleness instead of tail latency. The failover path
        always had this exposure during outages; hedging extends it to
        slow-shard moments. Readers that need the home shard's full
        answer (training reads go through `find`, not here) or strict
        read-your-writes should set HEDGED_READS=0."""
        groups: dict[int, list[str]] = {}
        for eid in dict.fromkeys(entity_ids):
            groups.setdefault(self._for_entity(eid), []).append(eid)

        def one(home: int, ids: list) -> dict:
            def make_call(c):
                def call():
                    return self._stores[c].find_entities_batch(
                        app_id,
                        entity_type,
                        ids,
                        channel_id=channel_id,
                        event_names=event_names,
                        limit_per_entity=limit_per_entity,
                        reversed=reversed,
                    )

                return call

            return self._hedged_call(self._replica_chain(home), make_call)

        res = self._broadcast(
            [(sx, one, (sx, ids)) for sx, ids in groups.items()]
        )
        out: dict = {}
        for part in res.values():
            out.update(part)
        return out

    def data_signature(self, app_id: int, channel_id: Optional[int] = None) -> str:
        res = self._broadcast(
            [
                (sx, s.data_signature, (app_id, channel_id))
                for sx, s in enumerate(self._stores)
            ]
        )
        return "|".join(res[sx] for sx in range(self.n_shards))

    def aggregate_properties(
        self,
        app_id: int,
        entity_type: str,
        channel_id: Optional[int] = None,
        **kw: Any,
    ) -> dict:
        # entities are shard-disjoint → per-shard aggregation unions
        # exactly (each child sees an entity's FULL $set/$unset history).
        # With REPLICAS > 1 each entity is attributed to its HOME shard
        # only: a successor's copy can be PARTIAL (pre-replication
        # history, or a logged replica-write failure) and must never
        # overwrite the home's complete aggregation. A down home's
        # entities are recovered from the first live successor instead —
        # best-available, possibly partial, and only reachable when the
        # broadcast itself was allowed to degrade.
        def agg(s: base.EventStore) -> dict:
            return s.aggregate_properties(
                app_id, entity_type, channel_id=channel_id, **kw
            )

        if self.replicas <= 1:
            res = self._broadcast(
                [(sx, agg, (s,)) for sx, s in enumerate(self._stores)],
                partial_ok=True,
            )
            out: dict = {}
            for sx in sorted(res):
                out.update(res[sx])
            return out
        # replicated: collect failures OURSELVES — a down home whose
        # successor answered is fully recoverable, so it must not raise
        # even without ALLOW_PARTIAL (the result is complete)
        futs = {
            sx: self._pool.submit(self._shard_call, sx, agg, st)
            for sx, st in enumerate(self._stores)
        }
        res, errs = {}, {}
        for sx, f in futs.items():
            try:
                res[sx] = f.result()
            except ShardDownError as e:
                errs[sx] = e
        degraded: list[int] = []
        out = {}
        for sx in range(self.n_shards):
            src = res.get(sx)
            if src is None:  # home down: first live successor's copy
                for c in self._replica_chain(sx)[1:]:
                    if c in res:
                        src = res[c]
                        break
            if src is None:  # whole chain down: only degradable
                if not self.allow_partial:
                    raise errs[sx]
                degraded.append(sx)
                log.warning("degraded aggregate: %s", errs[sx])
                continue
            out.update(
                {
                    k: v
                    for k, v in src.items()
                    if self._for_entity(k) == sx
                }
            )
        self.last_degraded_shards = degraded
        return out
