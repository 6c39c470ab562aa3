"""Plain reference for served recommendations: the score of item i for user
u is the dot product of their factor rows, in float32 with every product at
`Precision.HIGHEST`; a query's answer is the `num` best-scoring items that
its blacklist does not name. Straightforward jax.numpy over blocks of the
item table, nothing imported from the program; the tables are the
benchmark's own (made from --seed), the ids are `u<row>` / `i<row>`.

For each sampled query it returns what the comparison needs: the reference
scores of the items that were served, the sum of |u_k x_ik| for each of them
(the scale a rounding of the operands acts on), and the reference's own
`num`-th best allowed score.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def row_of(ident: str, prefix: str, n: int) -> int:
    """'i123' -> 123; -1 for anything that is not a valid id."""
    if not isinstance(ident, str) or not ident.startswith(prefix):
        return -1
    try:
        row = int(ident[len(prefix):])
    except ValueError:
        return -1
    return row if 0 <= row < n and ident == f"{prefix}{row}" else -1


@jax.jit
def _block_scores(q, items):
    return jnp.matmul(q, items.T, precision=HIGHEST)


def kth_best_allowed(user_rows: np.ndarray, black_rows: list[list[int]],
                     num: int, user_factors: np.ndarray,
                     item_factors: np.ndarray, block: int = 1 << 19):
    """(S,) the num-th best score among the items each query may get."""
    q = jnp.asarray(user_factors[user_rows])
    s = len(user_rows)
    best = jnp.full((s, num), -jnp.inf, jnp.float32)
    n_items = item_factors.shape[0]
    for lo in range(0, n_items, block):
        hi = min(lo + block, n_items)
        blk = item_factors[lo:hi]
        if hi - lo < block:  # one compiled shape: pad the last block
            blk = np.concatenate(
                [blk, np.zeros((block - (hi - lo), blk.shape[1]), blk.dtype)])
        scores = _block_scores(q, jnp.asarray(blk))
        dead = np.zeros((s, block), bool)
        dead[:, hi - lo:] = True
        for i, rows in enumerate(black_rows):
            for r in rows:
                if lo <= r < hi:
                    dead[i, r - lo] = True
        scores = jnp.where(jnp.asarray(dead), -jnp.inf, scores)
        top, _ = jax.lax.top_k(scores, num)
        best, _ = jax.lax.top_k(jnp.concatenate([best, top], axis=1), num)
    return np.asarray(best[:, num - 1])


def served_scores(user_rows: np.ndarray, served_rows: np.ndarray,
                  user_factors: np.ndarray, item_factors: np.ndarray):
    """Reference score and sum |u_k x_ik| of each served (query, item)."""
    q = jnp.asarray(user_factors[user_rows])  # (S, K)
    x = jnp.asarray(item_factors[np.maximum(served_rows, 0)])  # (S, N, K)
    ref = jnp.einsum("sk,snk->sn", q, x, precision=HIGHEST)
    scale = jnp.einsum("sk,snk->sn", jnp.abs(q), jnp.abs(x), precision=HIGHEST)
    return np.asarray(ref), np.asarray(scale)
