"""The Universal Recommender cell's data, all of it from `--seed`: the
correlator tables a trained model would hold, every user's event history,
and the fill of the event store through the store's own insert path.

The tables are MADE, not trained: `models/cco.py` `cross_occurrence_topn`
is dense (users x items) and cannot run at this catalogue (PERF.md section
4), as both ALS serving cells serve seeded factors. What is made keeps the
shape of a trained table: every item has up to `max_correlators_per_item`
correlators per indicator, drawn by item popularity, the tail of a row
padded with -1 (an item with fewer correlators), weights positive (an LLR
score is). Popularity is rank^-`popularity_exponent`; with 1/2 the inverse
distribution is closed-form (rank = n u^2), and the rarest item still stands
in ~25 items' rows; a user whose history is short or empty is answered with
the items that score, fewer than `num` or none, and the check expects that.

A user's history depends on (seed, user row) alone: per behaviour type a
heavy-tailed (log-normal) count with the source's mean, items by the same
popularity, one event a second, so "the latest 100 of a type" is defined.
"""

from __future__ import annotations

import datetime as dt
import threading

import numpy as np

from benchmarks.loadgen import user_stride

_T0 = dt.datetime(2017, 11, 25, tzinfo=dt.timezone.utc)  # the dataset's first day


def item_of_rank(ranks: np.ndarray, n_items: int) -> np.ndarray:
    """Popularity rank -> item row, the popular items spread over the
    table (as loadgen spreads its hot users)."""
    return (ranks.astype(np.int64) * user_stride(n_items)) % n_items


def draw_items(rng: np.random.Generator, n: int, cfg: dict) -> np.ndarray:
    """n item rows by popularity rank^-s, s = 1/2: rank = n_items u^2."""
    data = cfg["tables"]
    if float(data["popularity_exponent"]) != 0.5:
        raise ValueError("the inverse distribution is written for exponent 1/2")
    n_items = int(cfg["n_items"])
    u = rng.random(n)
    ranks = np.minimum((n_items * u * u).astype(np.int64), n_items - 1)
    return item_of_rank(ranks, n_items)


def make_correlators(cfg: dict, seed: int, threads: int = 8) -> list:
    """[(idx (I, T) int32 -1 padded, weights (I, T) float32), ...], one an
    indicator, filled in row chunks by `threads` generators spawned from
    the seed — the chunking is fixed, so the tables depend on the seed
    alone."""
    n_items = int(cfg["n_items"])
    top_n = int(cfg["algorithm"]["max_correlators_per_item"])
    data = cfg["tables"]
    max_pad = int(data["max_pad_slots"])
    out = []
    for m, _name in enumerate(cfg["indicators"]):
        idx = np.empty((n_items, top_n), np.int32)
        weights = np.empty((n_items, top_n), np.float32)
        chunks = 64
        bounds = np.linspace(0, n_items, chunks + 1).astype(np.int64)
        seeds = np.random.SeedSequence([seed % (2**32), 41, m]).spawn(chunks)

        def fill(ids, idx=idx, weights=weights, bounds=bounds, seeds=seeds):
            for c in ids:
                lo, hi = int(bounds[c]), int(bounds[c + 1])
                rng = np.random.default_rng(seeds[c])
                rows = hi - lo
                idx[lo:hi] = draw_items(rng, rows * top_n, cfg).reshape(
                    rows, top_n)
                w = rng.standard_exponential((rows, top_n), dtype=np.float32)
                w *= np.float32(data["weight_scale"])
                w += np.float32(data["weight_floor"])
                # an item with fewer correlators: the row's tail is -1
                live = top_n - rng.integers(0, max_pad + 1, rows)
                dead = np.arange(top_n)[None, :] >= live[:, None]
                idx[lo:hi][dead] = -1
                w[dead] = 0.0
                weights[lo:hi] = w

        workers = [threading.Thread(target=fill, args=(range(w, chunks, threads),))
                   for w in range(threads)]
        for w in workers:
            w.start()
        for w in workers:
            w.join()
        out.append((idx, weights))
    return out


def user_history(cfg: dict, seed: int, user_row: int) -> dict:
    """{indicator: item rows, oldest first}: the user's WHOLE history of
    each behaviour type (the engine reads the latest `max_query_events`)."""
    h = cfg["history"]
    rng = np.random.default_rng([seed % (2**32), 977, int(user_row)])
    out = {}
    for name in cfg["indicators"]:
        mean = float(h["mean_events"][name])
        sigma = float(h["lognormal_sigma"])
        n = int(rng.lognormal(np.log(mean) - sigma * sigma / 2.0, sigma))
        n = min(n, int(h["store_cap"]))
        out[name] = draw_items(rng, n, cfg)
    return out


def history_events(cfg: dict, user: str, history: dict) -> list:
    """The `Event`s of one user's history: event j of a type happened j
    seconds (times the number of types) after the first, so every type's
    order in time is its order in the array."""
    from predictionio_tpu.data.event import Event

    names = list(cfg["indicators"])
    events = []
    for m, name in enumerate(names):
        for j, item in enumerate(history[name]):
            events.append(Event(
                event=name, entity_type="user", entity_id=user,
                target_entity_type="item", target_entity_id=f"i{int(item)}",
                event_time=_T0 + dt.timedelta(seconds=j * len(names) + m),
            ))
    return events


class HistoryStore:
    """The histories the run has put into the event store, kept beside it
    as arrays for the reference: `ensure(rows)` inserts the whole history of
    every user row not yet there, through `EventStore.insert_batch`."""

    def __init__(self, cfg: dict, seed: int, storage, app_id: int):
        self.cfg, self.seed = cfg, seed
        self.events = storage.get_events()
        self.app_id = app_id
        self.by_user: dict[int, dict] = {}
        self.n_events = 0

    def ensure(self, user_rows) -> int:
        added = 0
        for row in dict.fromkeys(int(r) for r in user_rows):
            if row in self.by_user:
                continue
            history = user_history(self.cfg, self.seed, row)
            batch = history_events(self.cfg, f"u{row}", history)
            self.events.insert_batch(batch, self.app_id)
            self.by_user[row] = history
            added += len(batch)
        self.n_events += added
        return added
