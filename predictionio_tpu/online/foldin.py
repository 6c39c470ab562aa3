"""Fold-in updater: fresh events → incremental ALS model updates.

Converts a tick's worth of consumed events into a copy-on-write update
of the serving `ALSModel`: every dirty user's FULL event history is
re-read (indexed per-entity lookup) and the user's factor row re-solved
against the fixed item factors via `models/als.py:fold_in_rows`; new
items get rows appended and solved symmetrically against the updated
user factors. Re-solving from full history makes a fold idempotent —
replaying a crashed tick recomputes the same rows — which is what lets
the consumer's durable cursor give exactly-once *accounting* without
two-phase commit.

Growth is amortized: vocabularies and factor matrices grow in
`grow_chunk` row chunks, so a steady trickle of new users costs O(1)
amortized copies, not O(n) per event. The published model is a NEW
object sharing the unchanged side's arrays AND its staged device cache
(no re-transfer of a factor matrix that didn't change).
"""

from __future__ import annotations

import dataclasses
import logging
from dataclasses import dataclass, field
from typing import Any, Optional, Sequence

import numpy as np

import predictionio_tpu.resilience.faults as _faults
from predictionio_tpu.data.storage.base import EventQuery

log = logging.getLogger(__name__)


@dataclass
class FoldInConfig:
    """Event→edge translation knobs (mirrors the recommendation
    DataSource's semantics so folded rows match what a retrain derives)."""

    entity_type: str = "user"
    target_entity_type: str = "item"
    event_names: tuple[str, ...] = ("rate", "buy")
    rate_event: str = "rate"  # carries value_prop; everything else weighs 1.0
    value_prop: str = "rating"
    default_value: float = 1.0
    # per-tick cap on NEW-item solves: item history reads are
    # target-entity scans (no index), so a flood of new items spreads
    # over several ticks instead of stalling one
    max_items_per_tick: int = 64
    # factor matrices/vocabs grow in row chunks of this size (amortized)
    grow_chunk: int = 256


@dataclass
class FoldStats:
    users_folded: int = 0
    items_folded: int = 0
    users_added: int = 0
    items_added: int = 0
    edges: int = 0
    # item ids still awaiting a solve AFTER this result publishes; the
    # consumer commits this back via `commit_pending` only on a
    # successful publish — committing earlier would strand the carry
    # when a drift breach or a lost swap race discards the result
    pending_after: list = dataclasses.field(default_factory=list)

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["pending_after"] = len(self.pending_after)
        return d


def _grown(arr: np.ndarray, n_rows: int, chunk: int) -> np.ndarray:
    """Copy-on-write growth: a fresh array sized up to the next chunk
    multiple ≥ n_rows, old rows copied, new rows zero. Always copies —
    the previous model's readers keep their array untouched."""
    cap = max(n_rows, arr.shape[0])
    cap = ((cap + chunk - 1) // chunk) * chunk if cap > arr.shape[0] else cap
    out = np.zeros((cap, arr.shape[1]), np.float32)
    out[: arr.shape[0]] = arr
    return out


class ALSFoldIn:
    """Applies dirty-entity batches to an ALS-shaped model: anything with
    `.factors` carrying user/item factors + vocabs and a
    `with_factors(factors, carry)` that builds its successor (the
    recommendation/similarproduct/ecommerce family's models)."""

    def __init__(self, config: Optional[FoldInConfig] = None):
        self.config = config or FoldInConfig()
        # new items beyond max_items_per_tick carry over to later
        # ticks' solve sets (in tick order) — without this they would
        # keep zero factor rows until the next retrain. Mutated ONLY
        # via commit_pending (after a successful publish); apply()
        # itself is read-only on it so a discarded result cannot drop
        # the carry. In-memory by design: a consumer restart loses the
        # list, and those rows stay zero (never mis-ranked, score 0)
        # until a retrain or a new event re-dirties them.
        self._pending_item_solves: list[str] = []

    @property
    def pending_items(self) -> list[str]:
        return list(self._pending_item_solves)

    def commit_pending(self, pending: list) -> None:
        """Adopt the carry list of a PUBLISHED fold result."""
        self._pending_item_solves = list(pending)

    # -- model discovery ----------------------------------------------------
    @staticmethod
    def find_model(runtime) -> tuple[Optional[int], Any]:
        """(index, model) of the first fold-capable model in the runtime
        (duck-typed: no engine imports on this control path)."""
        for i, m in enumerate(getattr(runtime, "models", ()) or ()):
            f = getattr(m, "factors", None)
            if f is None or not hasattr(m, "with_factors"):
                continue
            if all(
                hasattr(f, a)
                for a in (
                    "user_factors", "item_factors", "user_vocab",
                    "item_vocab", "params",
                )
            ):
                return i, m
        return None, None

    # -- event → edge translation -------------------------------------------
    def _value(self, event) -> float:
        if event.event == self.config.rate_event:
            v = event.properties.to_dict().get(self.config.value_prop)
            if isinstance(v, (int, float)):
                return float(v)
        return float(self.config.default_value)

    def _relevant(self, event) -> bool:
        return (
            event.event in self.config.event_names
            and event.entity_type == self.config.entity_type
            and event.target_entity_type == self.config.target_entity_type
            and event.target_entity_id is not None
        )

    def dirty_entities(self, events) -> tuple[list[str], list[str]]:
        """(user ids, target item ids) touched by the relevant events,
        first-seen order preserved (deterministic row assignment)."""
        users: dict[str, None] = {}
        items: dict[str, None] = {}
        for e in events:
            if self._relevant(e):
                users.setdefault(e.entity_id, None)
                items.setdefault(e.target_entity_id, None)
        return list(users), list(items)

    # -- the apply tick -----------------------------------------------------
    def apply(
        self,
        storage,
        app_id: int,
        channel_id: Optional[int],
        runtime,
        events: Sequence,
    ):
        """One fold tick: returns (new_runtime, new_model, FoldStats), or
        None when nothing relevant changed (cursor still advances)."""
        # only the USER side comes from dirty_entities here: the item
        # solve set derives from the re-read histories below (which also
        # see items referenced by earlier events of a dirty user)
        dirty_users, _ = self.dirty_entities(events)
        if not dirty_users:
            return None
        ix, model = self.find_model(runtime)
        if model is None:
            log.warning(
                "online fold-in: no fold-capable model in runtime; "
                "events consumed without folding"
            )
            return None

        from predictionio_tpu.models import als

        factors = model.factors
        params = factors.params
        cfg = self.config
        store = storage.get_events()

        # full per-user histories (indexed read): state-based re-solve
        histories = store.find_entities_batch(
            app_id,
            cfg.entity_type,
            dirty_users,
            channel_id=channel_id,
            event_names=list(cfg.event_names),
            reversed=False,
        )
        user_edges: dict[str, dict[str, float]] = {}
        for uid, evs in histories.items():
            agg: dict[str, float] = {}
            for e in evs:
                if not self._relevant(e):
                    continue
                # duplicate (user, item) pairs SUM, matching
                # EventFrame.interactions(dedupe="sum") at train time
                agg[e.target_entity_id] = (
                    agg.get(e.target_entity_id, 0.0) + self._value(e)
                )
            if agg:
                user_edges[uid] = agg
        if not user_edges:
            return None

        stats = FoldStats()
        user_vocab = factors.user_vocab.to_dict()
        item_vocab = factors.item_vocab.to_dict()

        # vocab growth (users + every referenced item), amortized chunks
        new_items: list[str] = []
        for uid in user_edges:
            if uid not in user_vocab:
                user_vocab[uid] = len(user_vocab)
                stats.users_added += 1
        for agg in user_edges.values():
            for iid in agg:
                if iid not in item_vocab:
                    item_vocab[iid] = len(item_vocab)
                    new_items.append(iid)
                    stats.items_added += 1

        # item solve set: carried-over overflow first, then this tick's
        # new items; the remainder carries to the next tick. A carried
        # id MISSING from the vocab (a retrain whose data snapshot
        # predates the id swapped in) re-enters as a new item — its
        # events are behind the cursor, so dropping it here would
        # strand it until the next retrain. Decided BEFORE choosing
        # whether the item matrix copies — writing a pending item's row
        # must never mutate the published array in place.
        for iid in self._pending_item_solves:
            if iid not in item_vocab:
                item_vocab[iid] = len(item_vocab)
                new_items.append(iid)
                stats.items_added += 1
        carried = [
            i for i in self._pending_item_solves if i in item_vocab
        ]
        item_candidates = list(dict.fromkeys(carried + new_items))
        solve_items = item_candidates[: cfg.max_items_per_tick]
        uf = _grown(factors.user_factors, len(user_vocab), cfg.grow_chunk)
        items_changed = bool(new_items) or bool(solve_items)
        itf = (
            _grown(factors.item_factors, len(item_vocab), cfg.grow_chunk)
            if items_changed
            else factors.item_factors
        )

        # -- user side: solve against FIXED item factors -------------------
        rows: list[int] = []
        edge_lists: list[list[tuple[int, float]]] = []
        for uid, agg in user_edges.items():
            edges = [
                (item_vocab[iid], v)
                for iid, v in agg.items()
                if item_vocab[iid] < itf.shape[0]
            ]
            stats.edges += len(edges)
            rows.append(user_vocab[uid])
            edge_lists.append(edges)
        solved = als.fold_in_rows(itf, edge_lists, params)
        # chaos seam (ISSUE 9): "corrupt" scrambles the folded rows so the
        # drift guard has something real to catch; "error" fails the tick
        # (the consumer retries — the cursor never advanced)
        if _faults.fire("online.fold", corruptable=True) == "corrupt":
            solved = solved * 40.0 + 7.0
        uf[np.asarray(rows, np.int64)] = solved
        stats.users_folded = len(rows)

        # -- item side (symmetric): solve NEW items against updated users --
        dirty_items = None
        if solve_items:
            dirty_items = self._solve_item_rows(
                store, app_id, channel_id, solve_items,
                user_vocab, item_vocab, uf, itf, params, stats,
            )
        stats.pending_after = item_candidates[cfg.max_items_per_tick:]

        # -- copy-on-write publish ------------------------------------------
        # publish EXACT vocab-sized views (capacity padding must not leak
        # phantom zero-factor items into recommend's score matrix); the
        # backing buffers are never mutated after publish — the next tick
        # copies into fresh ones
        from predictionio_tpu.data.store.bimap import BiMap

        new_factors = dataclasses.replace(
            factors,
            user_factors=uf[: len(user_vocab)],
            item_factors=itf[: len(item_vocab)],
            user_vocab=BiMap(user_vocab),
            item_vocab=BiMap(item_vocab),
        )
        new_model = self._clone_model(
            model, new_factors, items_changed,
            dirty_users=(rows, solved) if rows else None,
            dirty_items=dirty_items,
        )
        models = list(runtime.models)
        models[ix] = new_model
        new_runtime = dataclasses.replace(runtime, models=models)
        return new_runtime, new_model, stats

    def _solve_item_rows(
        self, store, app_id, channel_id, solve_items,
        user_vocab, item_vocab, uf, itf, params, stats,
    ):
        """Solve `solve_items`' factor rows (writes into `itf`, which
        the caller has already copied) against the user factors `uf` —
        the symmetric half of the fold, shared by apply/apply_pending.
        Returns the (rows, solved values) actually written so the
        publish can row-update a staged serving state (ISSUE 11)."""
        from predictionio_tpu.models import als

        cfg = self.config
        item_rows: list[int] = []
        item_edge_lists: list[list[tuple[int, float]]] = []
        for iid in solve_items:
            edges: dict[int, float] = {}
            for e in store.find(EventQuery(
                app_id=app_id,
                channel_id=channel_id,
                event_names=list(cfg.event_names),
                entity_type=cfg.entity_type,
                target_entity_type=cfg.target_entity_type,
                target_entity_id=iid,
            )):
                urow = user_vocab.get(e.entity_id)
                if urow is not None and urow < uf.shape[0]:
                    edges[urow] = edges.get(urow, 0.0) + self._value(e)
            item_rows.append(item_vocab[iid])
            item_edge_lists.append(list(edges.items()))
        isolved = als.fold_in_rows(uf, item_edge_lists, params)
        if _faults.fire("online.fold", corruptable=True) == "corrupt":
            isolved = isolved * 40.0 + 7.0
        itf[np.asarray(item_rows, np.int64)] = isolved
        stats.items_folded = len(item_rows)
        return item_rows, isolved

    def apply_pending(
        self, storage, app_id: int, channel_id: Optional[int], runtime
    ):
        """Item-only fold pass for an IDLE stream: drains carried-over
        item solves so a quiet tail cannot strand overflow items at
        zero factor rows. Same return/commit contract as `apply`."""
        if not self._pending_item_solves:
            return None
        ix, model = self.find_model(runtime)
        if model is None:
            return None
        factors = model.factors
        item_vocab = factors.item_vocab.to_dict()
        # ids not (yet) in the published vocab came from a discarded
        # tick; they re-enter through apply()'s new_items when their
        # events re-fold, so they stay on the carry untouched here
        solvable = [
            i for i in self._pending_item_solves if i in item_vocab
        ]
        solve_items = solvable[: self.config.max_items_per_tick]
        if not solve_items:
            return None
        stats = FoldStats()
        stats.pending_after = [
            i for i in self._pending_item_solves if i not in solve_items
        ]
        user_vocab = factors.user_vocab.to_dict()
        uf = factors.user_factors
        itf = factors.item_factors.copy()  # COW: rows will be written
        dirty_items = self._solve_item_rows(
            storage.get_events(), app_id, channel_id, solve_items,
            user_vocab, item_vocab, uf, itf, factors.params, stats,
        )
        new_factors = dataclasses.replace(factors, item_factors=itf)
        new_model = self._clone_model(
            model, new_factors, True, users_changed=False,
            dirty_items=dirty_items,
        )
        models = list(runtime.models)
        models[ix] = new_model
        new_runtime = dataclasses.replace(runtime, models=models)
        return new_runtime, new_model, stats

    @staticmethod
    def _clone_model(
        model, new_factors, items_changed: bool, users_changed: bool = True,
        dirty_users=None, dirty_items=None,
    ):
        """New model around the folded factors: `model.with_factors`,
        which also carries the model's staged serving state
        (`ResidentServing.adopt`, either tier) by publishing the tick's
        dirty rows device-side — a tick re-transfers its dirty rows,
        never a factor matrix. Carried ONLY when every changed side has
        row attribution: a side changed without rows cannot be
        expressed as row writes, and the clone restages lazily instead
        of serving stale factors."""
        users_safe = not users_changed or dirty_users is not None
        items_safe = not items_changed or dirty_items is not None
        carry = None
        if users_safe and items_safe:
            carry = (
                dirty_users if users_changed else None,
                dirty_items if items_changed else None,
            )
        return model.with_factors(new_factors, carry)
