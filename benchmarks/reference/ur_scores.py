"""The plain reference of the Universal Recommender's serving: what ONE
query's answer is, from the events the set-up inserted for its user.

Per indicator the user's latest `depth` targets; an item's score is the sum
over indicators, over the item's correlators, of the weight where the
correlator is in that history (a correlator listed twice counts twice, one
that is -1 never); the user's seen primary items (the latest `depth`, as the
engine reads them) and the query's blacklist are removed; the `num` best
with a positive score are the answer.

Straight numpy and `jax.numpy`, float32 under
`jax.default_matmul_precision("highest")`, in item blocks so that a
4.16 M x 50 table fits; no kernel, no scan, no membership table, no query
batch, and no code of `predictionio_tpu/models/cco.py` (the program's
`score_history` is the program's). Membership is a plain comparison of every
correlator with every history entry. The tables are the host arrays the run
made from its seed, as they were before staging: (n_items, T), -1 padded.

For each sampled query the comparison needs the reference scores of the
items that were served (`served_scores`: those items' rows alone) and the
reference's own `num` best allowed scores over the WHOLE catalogue
(`best_allowed`: one pass over the tables, a block at a time, every
sampled query scored on each block while it is on the device).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 1 << 18  # item rows a block: 256 Ki x 50 x 4 B = 52 MB an array
NEVER = -2  # pads a history: no correlator is -2 (-1 pads a table row)


def row_of(name, prefix: str, n: int) -> int:
    """`i123` -> 123; -1 for anything that is not a row of the table."""
    if not isinstance(name, str) or not name.startswith(prefix):
        return -1
    try:
        row = int(name[len(prefix):])
    except ValueError:
        return -1
    return row if 0 <= row < n and name == f"{prefix}{row}" else -1


def latest(history, depth: int) -> np.ndarray:
    """The latest `depth` targets of a history given oldest first."""
    history = np.asarray(history, np.int64)
    return history[max(len(history) - depth, 0):]


def padded(history, depth: int) -> np.ndarray:
    """(depth,) int32: the latest `depth` targets, padded with NEVER."""
    out = np.full(depth, NEVER, np.int32)
    recent = latest(history, depth)
    out[: len(recent)] = recent
    return out


@jax.jit
def _block_scores(idx, weights, history):
    """(rows,) float32: sum of the weights whose correlator is in the
    history. idx (rows, T) int32, weights (rows, T) float32, history (H,)."""
    hit = jnp.any(idx[:, :, None] == history[None, None, :], axis=2)
    return jnp.sum(jnp.where(hit & (idx >= 0), weights, jnp.float32(0.0)),
                   axis=1)


def scores(tables: list, histories: list, depth: int) -> np.ndarray:
    """(n_items,) float32: every item's score for one user. `tables` is
    [(idx (I, T), weights (I, T)), ...], `histories` the user's targets per
    indicator, oldest first, in the same order."""
    n_items = tables[0][0].shape[0]
    total = np.zeros(n_items, np.float32)
    with jax.default_matmul_precision("highest"):
        for (idx, weights), history in zip(tables, histories):
            h = jnp.asarray(padded(history, depth))
            for lo in range(0, n_items, BLOCK):
                total[lo:lo + BLOCK] += np.asarray(_block_scores(
                    jnp.asarray(idx[lo:lo + BLOCK]),
                    jnp.asarray(weights[lo:lo + BLOCK], jnp.float32), h))
    return total


def served_scores(tables: list, histories: list, depth: int,
                  served_rows) -> np.ndarray:
    """(len(served_rows),) float32: the reference score of each served
    item, from those items' rows alone."""
    rows = np.asarray(served_rows, np.int64)
    return scores([(idx[rows], weights[rows]) for idx, weights in tables],
                  histories, depth)


def top(total: np.ndarray, dead, num: int):
    """(item rows, scores): the `num` best items that `dead` does not name
    and that score above 0, best first, ties to the lower row."""
    masked = np.where(total > 0, total, -np.inf)
    masked[np.asarray(list(dead), np.int64)] = -np.inf
    order = np.argsort(-masked, kind="stable")[:num]
    order = order[np.isfinite(masked[order])]
    return order, total[order]


@partial(jax.jit, static_argnames=("num",))
def _block_best(blocks, histories, dead, lo, *, num):
    """(S, num) float32: each query's `num` best scores on one block of
    item rows [lo, lo + rows), the rows its `dead` list names set to 0.
    One query after another (`lax.map`): a query's pass is `_block_scores`
    an indicator, as in `scores`."""
    rows = blocks[0][0].shape[0]

    def one(args):
        hists, not_allowed = args
        total = sum(_block_scores(ix, w, hists[m])
                    for m, (ix, w) in enumerate(blocks))
        here = not_allowed - lo
        here = jnp.where((here >= 0) & (here < rows), here, rows)
        total = total.at[here].set(0.0, mode="drop")
        return jax.lax.top_k(total, num)[0]

    return jax.lax.map(one, (histories, dead))


def best_allowed(tables: list, histories: list, dead: list, num: int,
                 depth: int) -> np.ndarray:
    """(S, num) float32, ascending: for each of S queries the `num` best
    scores among the items it may get, 0 where fewer score above 0 — so
    the entries above 0 count the items a right answer holds.
    `histories[s]` is that query's targets per indicator, `dead[s]` the item
    rows it may not get. Each block of the tables goes to the device once
    and every query is scored on it."""
    n_items = tables[0][0].shape[0]
    n = len(histories)
    padded_n = -(-n // 64) * 64  # one compiled shape for nearby sample sizes
    hists = np.full((padded_n, len(tables), depth), NEVER, np.int32)
    for s, per_query in enumerate(histories):
        for m, h in enumerate(per_query):
            hists[s, m] = padded(h, depth)
    widest = max((len(d) for d in dead), default=0)
    not_allowed = np.full((padded_n, -(-max(widest, 1) // 8) * 8), -1, np.int32)
    for s, d in enumerate(dead):
        not_allowed[s, : len(d)] = sorted(d)
    hists, not_allowed = jnp.asarray(hists), jnp.asarray(not_allowed)
    best = np.zeros((padded_n, num), np.float32)
    with jax.default_matmul_precision("highest"):
        for lo in range(0, n_items, BLOCK):
            hi = min(lo + BLOCK, n_items)
            blocks = []
            for idx, weights in tables:
                ix, w = idx[lo:hi], np.asarray(weights[lo:hi], np.float32)
                if hi - lo < BLOCK and n_items > BLOCK:
                    # one compiled shape: pad the last block with rows
                    # that have no correlator
                    pad = BLOCK - (hi - lo)
                    ix = np.concatenate(
                        [ix, np.full((pad, ix.shape[1]), -1, ix.dtype)])
                    w = np.concatenate(
                        [w, np.zeros((pad, w.shape[1]), np.float32)])
                blocks.append((jnp.asarray(ix), jnp.asarray(w)))
            block_best = np.asarray(_block_best(
                tuple(blocks), hists, not_allowed, jnp.int32(lo),
                num=min(num, hi - lo if n_items <= BLOCK else BLOCK)))
            merged = np.sort(np.concatenate([best, block_best], axis=1), axis=1)
            best = merged[:, -num:]
    return best[:n]
