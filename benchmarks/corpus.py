"""Train traffic: a seeded implicit-feedback interaction list.

The recipe is chip_smoke.py `make_corpus`'s (PR 21; the original stays where
it is until a later PR deletes it): Dirichlet(0.3) popularity on both sides,
independent (user, item) draws made UNIQUE, and one guaranteed pair for every
user and every item, so that the trained width is the configuration's and not
whatever the draw happened to touch. Values are 1.0: the configuration
binarises.

The sampler is not the original's. Every run pays it as set-up, and
`rng.choice(n, 65 M, p=...)` is a binary search per draw (45 s for this
cell's 56.9 M pairs on the chip's host, PR 24). A sequence of independent
draws from p is the same thing as multinomial counts laid out in a uniformly
random order, so the users come as `repeat(arange, multinomial)` (sorted:
the order is restored by the final shuffle) and the items as a shuffled
`repeat(arange, multinomial)`: the same distribution, a quarter of the time.

The same `seed` gives the same list; every seed gives the same sizes, so the
amount of work a job does never depends on the seed.
"""

from __future__ import annotations

import numpy as np


def _sorted_unique(keys: np.ndarray) -> np.ndarray:
    keys.sort()
    keep = np.empty(keys.size, bool)
    keep[0] = True
    np.not_equal(keys[1:], keys[:-1], out=keep[1:])
    return keys[keep]


def make_corpus(n_users: int, n_items: int, n_events: int, seed: int):
    """(rows int32, cols int32, vals float32) with `n_events` unique pairs."""
    if n_events < n_users + n_items:
        raise ValueError("n_events must cover every user and every item once")
    if n_events > n_users * n_items // 2:
        raise ValueError("n_events over half of the matrix: draw would stall")
    rng = np.random.default_rng([seed % (2**32), 57])
    user_p = rng.dirichlet(np.full(n_users, 0.3))
    item_p = rng.dirichlet(np.full(n_items, 0.3))

    def draw(n: int) -> np.ndarray:
        """n independent (user, item) draws as keys user * n_items + item."""
        keys = np.repeat(
            np.arange(n_users, dtype=np.int64), rng.multinomial(n, user_p))
        items = np.repeat(
            np.arange(n_items, dtype=np.int32), rng.multinomial(n, item_p))
        rng.shuffle(items)
        keys *= n_items
        keys += items
        return keys

    cover_u = np.arange(n_users, dtype=np.int64) * n_items + rng.choice(
        n_items, n_users, p=item_p)
    cover_i = rng.choice(n_users, n_items, p=user_p).astype(
        np.int64) * n_items + np.arange(n_items, dtype=np.int64)
    cover = _sorted_unique(np.concatenate([cover_u, cover_i]))
    keys = cover
    while keys.size < n_events:
        more = draw(int((n_events - keys.size) * 1.15) + 1000)
        keys = _sorted_unique(np.concatenate([keys, more]))
    # every cover pair stays; of the others a random subset goes
    surplus = keys.size - n_events
    if surplus:
        others = np.ones(keys.size, bool)
        others[np.searchsorted(keys, cover)] = False
        others = np.flatnonzero(others)
        keep = np.ones(keys.size, bool)
        keep[others[rng.choice(others.size, surplus, replace=False)]] = False
        keys = keys[keep]
    rng.shuffle(keys)
    rows = (keys // n_items).astype(np.int32)
    cols = (keys % n_items).astype(np.int32)
    return rows, cols, np.ones(n_events, np.float32)
