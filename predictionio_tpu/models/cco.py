"""Correlated cross-occurrence (CCO) with log-likelihood-ratio scoring.

The compute core of the Universal Recommender (external template
actionml/template-scala-parallel-universal-recommendation, which delegates to Mahout's SimilarityAnalysis.cooccurrences on Spark).

TPU-first design: the cross-occurrence count matrix between a primary
interaction matrix P (users × items) and a secondary indicator matrix S
(users × things) is EXACTLY PᵀS on binarized indicators — one dense MXU
matmul — instead of Mahout's sparse row-similarity shuffle. Dunning's LLR
then scores every (item, thing) pair elementwise on device, and a masked
top-k keeps each item's strongest correlators. Multi-chip: shard the user
dimension over the mesh's data axis; GSPMD reduces the matmul's user
contraction with an ICI all-reduce (psum) — user-partitioned co-occurrence
counting, the TPU-native analogue of Mahout's map-side combining.

Counts stay exact in float32 (counts ≤ U < 2²⁴) with HIGHEST precision.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from predictionio_tpu.obs import devprof as _devprof
from predictionio_tpu.ops.topk import NEG_INF, masked_top_k


def _x_log_x(x: jax.Array) -> jax.Array:
    return jnp.where(x > 0, x * jnp.log(jnp.maximum(x, 1e-30)), 0.0)


def llr_scores(
    k11: jax.Array,  # (I, J) co-occurrence counts
    prim_totals: jax.Array,  # (I,) per-item event totals
    sec_totals: jax.Array,  # (J,) per-thing event totals
    n_users: jax.Array | float,
) -> jax.Array:
    """Dunning log-likelihood ratio of the 2×2 contingency per pair."""
    k12 = prim_totals[:, None] - k11
    k21 = sec_totals[None, :] - k11
    k22 = n_users - k11 - k12 - k21
    row_entropy = _x_log_x(k11 + k12) + _x_log_x(k21 + k22)
    col_entropy = _x_log_x(k11 + k21) + _x_log_x(k12 + k22)
    mat_entropy = (
        _x_log_x(k11) + _x_log_x(k12) + _x_log_x(k21) + _x_log_x(k22)
    )
    llr = 2.0 * (mat_entropy - row_entropy - col_entropy + _x_log_x(
        jnp.asarray(n_users, jnp.float32)
    ))
    return jnp.maximum(llr, 0.0)


@partial(jax.jit, static_argnames=("top_n", "exclude_diagonal"))
def _cco_topn(
    primary: jax.Array,  # (U, I_blk) binarized (possibly zero-padded rows)
    secondary: jax.Array,  # (U, J) binarized
    n_users: jax.Array,  # scalar — TRUE user count (padding rows excluded)
    diag_offset: jax.Array,  # scalar — primary block's start column
    *,
    top_n: int,
    exclude_diagonal: bool,
):
    counts = jax.lax.dot_general(
        primary, secondary,
        dimension_numbers=(((0,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
    )  # (I_blk, J) — MXU, user dim contracted (psum over dp shards)
    prim_totals = jnp.sum(primary, axis=0)
    sec_totals = jnp.sum(secondary, axis=0)
    llr = llr_scores(counts, prim_totals, sec_totals, n_users)
    exclude = counts <= 0  # never correlate never-co-occurring pairs
    if exclude_diagonal:
        # the diagonal of the GLOBAL (I, I) matrix: global row index =
        # diag_offset + local row (item blocking shifts the block)
        r = jnp.arange(llr.shape[0], dtype=jnp.int32)[:, None] + diag_offset
        c = jnp.arange(llr.shape[1], dtype=jnp.int32)[None, :]
        exclude = exclude | (r == c)
    vals, idx = masked_top_k(llr, top_n, exclude)
    idx = jnp.where(vals > 0.0, idx, -1)  # llr 0 → not a correlator
    return vals, idx


def edges_to_indicator(
    rows: np.ndarray, cols: np.ndarray, n_rows: int, n_cols: int
) -> np.ndarray:
    """Binarized dense indicator matrix from an edge list."""
    m = np.zeros((n_rows, n_cols), dtype=np.float32)
    m[rows, cols] = 1.0
    return m


_cco_topn = _devprof.instrument("cco.topn", _cco_topn)


def cross_occurrence_topn(
    primary: np.ndarray,  # (U, I)
    secondary: np.ndarray,  # (U, J)
    top_n: int,
    self_indicator: bool = False,
    mesh: Optional[jax.sharding.Mesh] = None,
    block_items: int = 8192,
) -> tuple[np.ndarray, np.ndarray]:
    """Per primary item: top correlator columns of `secondary` by LLR.

    Returns (scores (I, top_n), indices (I, top_n)) with -1 index padding.
    `self_indicator` excludes the diagonal (an item trivially co-occurs
    with itself).

    The primary item axis is processed in `block_items`-column blocks so
    the (I_blk, J) LLR intermediate stays bounded — a 100k-item catalog's
    dense (I, I) matrix alone would be 40 GB, past single-chip HBM. Rows
    are independent through LLR and top-k, so blocking is exact. (The
    Mahout reference handles this scale with sparse shuffles; blocking is
    the dense-MXU equivalent.)"""
    top_n = min(top_n, secondary.shape[1])
    true_n_users = primary.shape[0]
    n_items = primary.shape[1]
    if mesh is not None:
        # pad the user dim so it shards evenly; zero rows are inert in the
        # counts/totals and the true user count is passed separately for LLR
        from predictionio_tpu.parallel.mesh import pad_and_shard_rows

        p, s = pad_and_shard_rows(mesh, primary, secondary)
    else:
        p = jnp.asarray(primary)
        s = jnp.asarray(secondary)
    if n_items <= block_items:
        vals, idx = _cco_topn(
            p, s, jnp.float32(true_n_users), jnp.int32(0),
            top_n=top_n, exclude_diagonal=self_indicator,
        )
        return np.asarray(vals), np.asarray(idx)
    # one compiled program serves every block: pad the last block's
    # columns with zero items (counts 0 → excluded → idx -1)
    out_vals = np.empty((n_items, top_n), np.float32)
    out_idx = np.empty((n_items, top_n), np.int32)
    for lo in range(0, n_items, block_items):
        hi = min(lo + block_items, n_items)
        blk = p[:, lo:hi]
        if hi - lo < block_items:
            blk = jnp.pad(blk, ((0, 0), (0, block_items - (hi - lo))))
        vals, idx = _cco_topn(
            blk, s, jnp.float32(true_n_users), jnp.int32(lo),
            top_n=top_n, exclude_diagonal=self_indicator,
        )
        out_vals[lo:hi] = np.asarray(vals)[: hi - lo]
        out_idx[lo:hi] = np.asarray(idx)[: hi - lo]
    return out_vals, out_idx


def score_history(
    correlator_idx: np.ndarray,  # (I, top_n) int, -1 padded
    correlator_scores: np.ndarray,  # (I, top_n)
    history: np.ndarray,  # (H,) int — the user's recent things for this indicator
) -> np.ndarray:
    """Host-side single-query scoring: per-item sum of LLR over correlators
    present in the user's history. Kept as the reference implementation the
    device batch path (batch_score_topk) is tested against."""
    if len(history) == 0:
        return np.zeros(correlator_idx.shape[0], dtype=np.float32)
    hit = np.isin(correlator_idx, history) & (correlator_idx >= 0)
    return np.where(hit, correlator_scores, 0.0).sum(axis=1).astype(np.float32)


# ---------------------------------------------------------------------------
# Device-side batched serving (VERDICT r2 #5)
# ---------------------------------------------------------------------------

_SCORE_BLOCK_I = 8192  # item rows per scan step — bounds the gathered
# (block·top_n, B) intermediate at catalog scale


@partial(jax.jit, static_argnames=("j_sizes", "k", "mode", "packed"))
def _batch_score_topk_jit(
    corr_idx: tuple,  # per indicator: (I, T_m) int32, -1 padded
    corr_scores: tuple,  # per indicator: (I, T_m) float32
    histories: tuple,  # per indicator: (B, H_m) int32, -1 padded
    exclude: jax.Array,  # (B, E) int32 rows / (B, I_p/32) int32 words
    *,
    j_sizes: tuple,  # per indicator: its target-vocab size J_m (static)
    k: int,
    mode=None,  # resolved pallas mode for the fused tail (None = XLA)
    packed: bool = False,  # exclude arrived as bit-packed mask words
):
    """One device program for a whole query batch: per indicator, scatter
    each user's history into a (B, J+1) membership table, gather it at the
    correlator indices (item-row blocks scanned to bound memory), and
    accumulate weighted hits; then mask the per-query exclusion set and
    top-k. Replaces the per-(query × indicator) numpy loop — the UR
    serving hot path runs as ONE jit dispatch per micro-batch.

    The exclusion+top-k tail is the verb-agnostic fused kernel's
    precomputed-score mode (ISSUE 14): with `mode` set the accumulated
    total streams through `ops.recommend_pallas.fused_masked_topk` —
    no masked (B, I) score COPY, no (B, I) exclusion-mask
    materialization (the packed words / row list apply in registers).
    The XLA tail keeps identical semantics for exact mode parity."""
    from predictionio_tpu.ops import recommend_pallas as _rp

    n_items = corr_idx[0].shape[0]
    bsz = histories[0].shape[0]
    i_p = _rp.pad_items(n_items) if mode is not None else n_items
    total = jnp.zeros((bsz, i_p), jnp.float32)
    for idx, sc, hist, j in zip(corr_idx, corr_scores, histories, j_sizes):
        i, t = idx.shape
        hist_safe = jnp.where(hist >= 0, hist, j)
        member = jnp.zeros((bsz, j + 1), jnp.float32)
        member = member.at[
            jnp.arange(bsz)[:, None], hist_safe
        ].set(1.0)
        member = member.at[:, j].set(0.0)  # -1 padding slot is inert
        member_t = member.T  # (J+1, B) — row-gather layout
        i_pad = (-i) % _SCORE_BLOCK_I
        idx_p = jnp.pad(idx, ((0, i_pad), (0, 0)), constant_values=-1)
        sc_p = jnp.pad(sc, ((0, i_pad), (0, 0)))
        n_blk = (i + i_pad) // _SCORE_BLOCK_I
        idx_c = idx_p.reshape(n_blk, _SCORE_BLOCK_I, t)
        sc_c = sc_p.reshape(n_blk, _SCORE_BLOCK_I, t)

        def body(_, ch):
            ix, w0 = ch
            safe = jnp.where(ix >= 0, ix, j).reshape(-1)
            g = member_t[safe].reshape(_SCORE_BLOCK_I, t, bsz)
            w = jnp.where(ix >= 0, w0, 0.0)
            # HIGHEST: f32 LLR sums must match the host reference scorer —
            # default MXU bf16 would reorder close-scoring items
            return None, jnp.einsum(
                "itb,it->ib", g, w, precision=jax.lax.Precision.HIGHEST
            )

        _, outs = jax.lax.scan(body, None, (idx_c, sc_c))
        # pad rows beyond i carry only padded-correlator zeros, so the
        # i_p-wide slice is exact (they are dead in both tails anyway)
        total = total + outs.reshape(-1, bsz)[:i_p].T
    if mode is not None:
        return _rp.fused_masked_topk(
            total,
            mask_bits=exclude if packed else None,
            exclude_rows=None if packed else exclude,
            k=k, n_items=n_items, interpret=(mode == "interpret"),
        )
    if packed:
        ex_mask = _rp.unpack_mask_jnp(exclude, n_items)
    else:
        ex_safe = jnp.where(exclude >= 0, exclude, n_items)
        ex_mask = jnp.zeros((bsz, n_items + 1), bool)
        ex_mask = ex_mask.at[
            jnp.arange(bsz)[:, None], ex_safe
        ].set(True)
        ex_mask = ex_mask[:, :n_items]
    total = jnp.where(ex_mask, NEG_INF, total)
    return jax.lax.top_k(total, k)


# device profiling (ISSUE 3): the UR serving hot path is one executable
# per micro-batch shape; memory=True is safe — warmup covers the ladder
_batch_score_topk_jit = _devprof.instrument(
    "cco.batch_score_topk", _batch_score_topk_jit, memory=True
)


def batch_score_topk(
    indicator_tables: list,  # [(corr_idx jnp/np, corr_scores jnp/np, J), ...]
    histories: list,  # per indicator: (B, H) int32 np, -1 padded
    exclude: np.ndarray,  # (B, E) int32, -1 padded (item space)
    k: int,
    mode: str = "auto",
) -> tuple[np.ndarray, np.ndarray]:
    """Batched UR history scoring + exclusion + top-k in one device
    dispatch. Returns (scores (B, k), item indices (B, k)); entries with
    score <= 0 carry no LLR evidence (callers filter positive-only).

    `mode` gates the fused tail (resolve_mode contract: "auto" → tpu
    where the lowering runs / "interpret" for tests / None|"off" → the
    XLA tail). Narrow exclusion sets ride the kernel's row-list input
    untouched; wider ones bit-pack HOST-side (1/32 the f32-equivalent
    mask bytes over the wire and in HBM)."""
    from predictionio_tpu.ops import recommend_pallas as _rp

    resolved = _rp.resolve_mode(mode)
    exclude = np.asarray(exclude, np.int32)
    packed = False
    ex_dev = exclude
    if resolved is not None and exclude.shape[1] > _rp.ROWLIST_MAX:
        n_items = int(np.asarray(indicator_tables[0][0]).shape[0])
        i_p = _rp.pad_items(n_items)
        mask = np.zeros((exclude.shape[0], i_p), bool)
        for b in range(exclude.shape[0]):
            hits = exclude[b]
            hits = hits[(hits >= 0) & (hits < i_p)]
            mask[b, hits] = True
        ex_dev = _rp.pack_mask_np(mask, i_p)
        packed = True
    vals, idx = _batch_score_topk_jit(
        tuple(jnp.asarray(t[0]) for t in indicator_tables),
        tuple(jnp.asarray(t[1]) for t in indicator_tables),
        tuple(jnp.asarray(h) for h in histories),
        jnp.asarray(ex_dev),
        j_sizes=tuple(int(t[2]) for t in indicator_tables),
        k=k,
        mode=resolved,
        packed=packed,
    )
    return np.asarray(vals), np.asarray(idx)
