"""Shared host-side ranking helpers for serving paths.

The similarproduct and ecommerce templates rank a per-item score vector
after applying host-built business-rule masks, on the host (whether a
device round trip would win is unmeasured — ROADMAP D9); models/als.py's
jitted `recommend`/`similar_items` remain the batched device path the
recommendation engine uses. One NEG_INF convention, one implementation."""

from __future__ import annotations

import numpy as np

from predictionio_tpu.ops.topk import NEG_INF


def l2_normalize(factors: np.ndarray) -> np.ndarray:
    """Row-normalize a factor matrix for cosine scoring."""
    return factors / (np.linalg.norm(factors, axis=-1, keepdims=True) + 1e-9)


def top_k_indices(scores: np.ndarray, k: int) -> np.ndarray:
    """Indices of the k best scores, sorted descending, masked entries
    (≤ NEG_INF/2) dropped."""
    k = min(k, len(scores))
    if k <= 0:
        return np.empty(0, dtype=np.int64)
    top = np.argpartition(-scores, k - 1)[:k]
    top = top[np.argsort(-scores[top])]
    return top[scores[top] > NEG_INF / 2]


def exclusion_scores(
    scores: np.ndarray, excluded: np.ndarray
) -> np.ndarray:
    return np.where(excluded, NEG_INF, scores)


def top_k_filtered(
    scores: np.ndarray,
    k: int,
    exclude_idx=None,
    include_idx=None,
    positive_only: bool = False,
) -> np.ndarray:
    """Top-k with SPARSE exclusion/inclusion — no dense (I,) bool mask.

    `exclude_idx`: small index collection (seen history, blacklist,
    unavailable items). Over-fetches k + len(exclude) candidates then
    drops excluded ones, so per-query memory is O(k + |exclude|) beyond
    the score vector itself. `include_idx`: whitelist — only these
    indices compete (scores gathered, O(|include|)). `positive_only`
    drops non-positive scores (UR: zero LLR evidence is not a
    recommendation). Returns indices sorted by descending score."""
    if k <= 0 or len(scores) == 0:
        return np.empty(0, dtype=np.int64)
    ex = (
        np.unique(np.asarray(exclude_idx, dtype=np.int64))
        if exclude_idx is not None and len(exclude_idx)
        else None
    )
    if include_idx is not None:
        cand = np.unique(np.asarray(include_idx, dtype=np.int64))
        if ex is not None:
            cand = np.setdiff1d(cand, ex, assume_unique=True)
        cand_scores = scores[cand]
    else:
        m = k + (len(ex) if ex is not None else 0)
        if m >= len(scores):
            cand = np.arange(len(scores), dtype=np.int64)
        else:
            cand = np.argpartition(-scores, m - 1)[:m].astype(np.int64)
        if ex is not None:
            cand = cand[~np.isin(cand, ex, assume_unique=False)]
        cand_scores = scores[cand]
    keep = cand_scores > (0.0 if positive_only else NEG_INF / 2)
    cand, cand_scores = cand[keep], cand_scores[keep]
    top = np.argsort(-cand_scores, kind="stable")[:k]
    return cand[top]
