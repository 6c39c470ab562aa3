"""The comparisons that decide `correct`: numpy only, no program code."""

from __future__ import annotations

import numpy as np


def table_gap(program: np.ndarray, reference: np.ndarray) -> float:
    """||P - R||_F / ||R||_F over a whole factor table."""
    ref = np.asarray(reference, np.float64)
    diff = np.asarray(program, np.float64) - ref
    return float(np.linalg.norm(diff) / np.linalg.norm(ref))


def worst_row_gap(program: np.ndarray, reference: np.ndarray) -> float:
    """The worst single row: ||P_r - R_r|| against the reference's norm of
    that row or of the median row, whichever is larger (a row that is all
    but zero would otherwise decide)."""
    ref = np.asarray(reference, np.float64)
    diff = np.linalg.norm(np.asarray(program, np.float64) - ref, axis=1)
    norms = np.linalg.norm(ref, axis=1)
    return float(np.max(diff / np.maximum(norms, np.median(norms))))


def vocab_mismatches(vocab: dict, prefix: str, n: int) -> int:
    """Ids whose row is not the one the corpus gave them."""
    if len(vocab) != n:
        return abs(len(vocab) - n) + 1
    bad = 0
    for i in range(n):
        if vocab.get(f"{prefix}{i}") != i:
            bad += 1
    return bad
