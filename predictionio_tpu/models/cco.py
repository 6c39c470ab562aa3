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
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from predictionio_tpu.obs import devprof as _devprof
from predictionio_tpu.obs import spans as _spans
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
# Device-side batched serving (VERDICT r2 #5; re-formed in PR 35)
# ---------------------------------------------------------------------------
#
# A user's history names a few hundred things; an item scores only where
# one of them is among its correlators. So the tables are staged INVERTED
# — per indicator every (thing, item, weight) posting sorted by thing, a
# thing's postings contiguous — and a batch reads the postings of its
# histories' things alone, in fixed windows of `_WINDOW` consecutive
# postings (one contiguous read each), and adds their weights into the
# (B, I_p) total by scatter. The form this replaces gathered a membership
# table at EVERY correlator slot — 832 M look-ups a batch at 4.16 M items
# x 50 x 4 — which XLA's gather serves at ~130 M a second on a v5e: 6.3 s
# a batch at B = 1, 10.8 s at B = 8 (PERF.md, PR 35).

#: postings one window reads: a thing's list is read in windows of this
#: many consecutive postings (its last window masked beyond the list).
#: On a v5e a window costs ~2.6 us to fetch whatever its length and a slot
#: ~14 ns to add, live or masked (PERF.md, PR 35): at a median list of 45
#: postings and a mean of 170, 128 and 256 cost a query the same, and 128
#: leaves fewer dead slots
_WINDOW = 128
#: windows one device call takes, by the batch bucket (`call_windows`):
#: `_WINDOWS_PER_ROW` a row, at least the floor — the 160-odd windows of
#: one typical history — and at most the cap, which holds a full 64-row
#: bucket's typical plan; a batch with more makes further calls
_WINDOWS_PER_ROW = 128
_CALL_WINDOWS = (256, 4096)

#: exclusion row lists ride at one of two widths, so that warm-up can
#: cover every program a batch may pick: the narrow one for a seen-list
#: and a short blacklist, `ROWLIST_MAX` for anything up to the kernel's
#: cap; beyond that the ids ship as packed words. The kernel unrolls a
#: compare an id and tile, so the width is paid by every batch that ships
#: a list: over 4.16 M items the tail takes 1.05 / 1.69 / 4.57 ms at 8 ids
#: wide and 2.23 / 2.84 / 15.6 at 64 (B = 1 / 8 / 64, v5e; PERF.md, PR 35)
_ROWLIST_NARROW = 8


def _slots(indicator_tables) -> int:
    """Correlator slots of a table set: items x correlators, summed over
    the indicators."""
    return sum(int(np.prod(np.shape(t[0]))) for t in indicator_tables)


class StagedCorrelators(NamedTuple):
    """Correlator tables resident on the device, inverted: the item and
    the weight of every posting, an indicator's postings one block of the
    two arrays, sorted by thing within it (the -1 slots of a table sort
    first in its block and are never read; `_WINDOW` slots of padding
    close the arrays, so a window may over-read), and on the HOST the
    offsets that say where a thing's postings lie."""

    items: jax.Array  # (sum of I x T_m + _WINDOW,) int32
    weights: jax.Array  # (sum of I x T_m + _WINDOW,) float32
    offsets: tuple  # per indicator HOST (J_m + 1,) int64: thing j's
    # postings are [offsets[j], offsets[j + 1]) of the two arrays
    n_items: int  # live item rows
    rows_padded: int  # item rows of the (B, I_p) total: the pad rule's
    # (`recommend_pallas.pad_items`), so a tile of the fused tail divides
    n_items_device: jax.Array  # () int32: `n_items`, resident, so that
    # no batch transfers it

    @property
    def nbytes(self) -> int:
        """Device bytes (the offsets stay on the host)."""
        return int(self.items.nbytes + self.weights.nbytes)


def table_set_bytes(indicator_tables) -> int:
    """Bytes `stage_correlators` will make resident (an int32 item and
    a float32 weight a correlator slot, and a window of padding), from
    shapes alone."""
    return (_slots(indicator_tables) + _WINDOW) * 8


#: the widest batch bucket one dispatch takes (the engine chunks a longer
#: query list): its (B, I_p) total is the largest a batch allocates
MAX_BATCH = 64


def device_peak_bytes(indicator_tables) -> tuple[int, int, int]:
    """What serving a table set asks of the device at its peak, from
    shapes alone, in three terms: the resident postings
    (`table_set_bytes`); while staging, beside both resident arrays, the
    widest indicator's flat things and weights and the sort's item and
    weight outputs (four arrays of 4 B a slot: the 10.0 GB peak of a
    6.66 GB set on a v5e, PERF.md, PR 35); and a `MAX_BATCH`-row float32
    total, which a batch of the widest bucket holds beside the tables."""
    from predictionio_tpu.ops import recommend_pallas as _rp

    widest = max(_slots([t]) for t in indicator_tables)
    rows_padded = _rp.pad_items(int(np.shape(indicator_tables[0][0])[0]))
    return (
        table_set_bytes(indicator_tables),
        4 * widest * 4,
        MAX_BATCH * rows_padded * 4,
    )


@jax.jit
def _invert_jit(things, weights, top_n):  # lint: disable=jit-boundary —
    # staging, once a model: not a serving executable
    """(I x T,) things and weights in item-major order → the items and
    the weights sorted by thing."""
    items = jnp.arange(things.shape[0], dtype=jnp.int32) // top_n
    _, items, weights = jax.lax.sort((things, items, weights), num_keys=1)
    return items, weights


@partial(jax.jit, donate_argnums=(0, 1))
def _place_jit(all_items, all_weights, items, weights, base):  # lint: disable=jit-boundary —
    # staging, once a model: not a serving executable
    """One indicator's sorted postings into its block of the resident
    arrays, in place."""
    return (
        jax.lax.dynamic_update_slice(all_items, items, (base,)),
        jax.lax.dynamic_update_slice(all_weights, weights, (base,)),
    )


def stage_correlators(indicator_tables: list) -> StagedCorrelators:
    """[(corr_idx (I, T_m), corr_scores (I, T_m), J_m), …] → resident
    postings. One indicator at a time: its table goes to the device flat
    (no host copy where it is int32 / float32 already), is sorted by thing
    there and placed into its block of the two resident arrays, and the
    host counts each thing's postings for the offsets."""
    from predictionio_tpu.ops import recommend_pallas as _rp

    n_items = int(np.shape(indicator_tables[0][0])[0])
    slots = _slots(indicator_tables)
    all_items = jnp.zeros((slots + _WINDOW,), jnp.int32)
    all_weights = jnp.zeros((slots + _WINDOW,), jnp.float32)
    offsets, base = [], 0
    for cidx, csc, j in indicator_tables:
        flat = np.ascontiguousarray(cidx, np.int32).reshape(-1)
        # bin 0 counts the -1 slots, which sort before every thing, so
        # the running count up to bin j is where thing j's postings start
        # in the block and the last, all of them, where thing J - 1's end
        counts = np.bincount(flat + 1, minlength=int(j) + 1)[: int(j) + 1]
        offsets.append(base + np.cumsum(counts, dtype=np.int64))
        items, weights = _invert_jit(
            jax.device_put(flat),
            jax.device_put(
                np.ascontiguousarray(csc, np.float32).reshape(-1)),
            jnp.int32(np.shape(cidx)[1]),
        )
        all_items, all_weights = _place_jit(
            all_items, all_weights, items, weights, jnp.int32(base)
        )
        all_weights.block_until_ready()
        del items, weights
        base += flat.size
    return StagedCorrelators(
        all_items, all_weights, tuple(offsets), n_items,
        _rp.pad_items(n_items), jax.device_put(np.int32(n_items)),
    )


class Exclusion(NamedTuple):
    """A batch's per-query exclusion sets in the wire form its ids
    call for: "none" (no query excludes anything), "rows" ((B, E) int32
    ids, -1 padded; E is 8 or `ROWLIST_MAX`) or "mask" ((B, I_p/32)
    int32 packed words, for a query with more ids than a row list
    holds)."""

    form: str
    array: Optional[np.ndarray]

    @property
    def nbytes(self) -> int:
        return 0 if self.array is None else int(self.array.nbytes)


def exclusion_of(lists, bsz: int, rows_padded: int) -> Exclusion:
    """The form follows the ids the batch really carries (as
    `ShardedRuntime.recommend` chooses since PR 29), never a static
    worst case: `lists` holds one id list a live query, the bucket's
    other rows exclude nothing."""
    from predictionio_tpu.ops import recommend_pallas as _rp

    widest = max((len(r) for r in lists), default=0)
    if widest == 0:
        return Exclusion("none", None)
    if widest <= _rp.ROWLIST_MAX:
        width = (
            _ROWLIST_NARROW if widest <= _ROWLIST_NARROW
            else _rp.ROWLIST_MAX
        )
        rows = np.full((bsz, width), -1, np.int32)
        for b, ids in enumerate(lists):
            rows[b, : len(ids)] = ids
        return Exclusion("rows", rows)
    # straight to words: no (B, I_p) bool mask on the way
    words = np.zeros((bsz, rows_padded // 32), np.uint32)
    for b, ids in enumerate(lists):
        ids = np.asarray(ids, np.int64)
        ids = ids[(ids >= 0) & (ids < rows_padded)]
        np.bitwise_or.at(
            words[b], ids >> 5, np.uint32(1) << (ids & 31).astype(np.uint32)
        )
    return Exclusion("mask", words.view(np.int32))


def plan_windows(staged: StagedCorrelators, histories: list) -> np.ndarray:
    """The windows of postings a batch reads: (W, 3) int64 rows of
    (batch row, start, valid) — for every query and indicator each
    DISTINCT thing of the history (membership is a set: a thing seen
    twice counts once), its posting list cut into windows of `_WINDOW`;
    `start` is a position in the resident arrays, `valid` how many of
    the window's postings are the thing's. Host arithmetic on the
    offsets, no device work."""
    plans = []
    for hist, offsets in zip(histories, staged.offsets):
        hist = np.sort(np.asarray(hist, np.int64), axis=1)
        fresh = np.ones(hist.shape, bool)
        fresh[:, 1:] = hist[:, 1:] != hist[:, :-1]
        keep = fresh & (hist >= 0) & (hist < len(offsets) - 1)
        rows, _cols = np.nonzero(keep)
        things = hist[keep]
        starts = offsets[things]
        lens = offsets[things + 1] - starts
        n_win = -(-lens // _WINDOW)
        total = int(n_win.sum())
        if not total:
            continue
        of = np.repeat(np.arange(len(things)), n_win)  # window -> its thing
        within = np.arange(total) - np.repeat(np.cumsum(n_win) - n_win, n_win)
        plan = np.empty((total, 3), np.int64)
        plan[:, 0] = rows[of]
        plan[:, 1] = starts[of] + within * _WINDOW
        plan[:, 2] = np.minimum(_WINDOW, lens[of] - within * _WINDOW)
        plans.append(plan)
    if not plans:
        return np.zeros((0, 3), np.int64)
    return np.concatenate(plans)


def call_windows(bsz: int) -> int:
    """Windows one device call takes in a `bsz`-row bucket: 256, 1,024,
    4,096 for the buckets 1, 8, 64."""
    floor, cap = _CALL_WINDOWS
    return min(max(_WINDOWS_PER_ROW * bsz, floor), cap)


def plan_calls(plan: np.ndarray, bsz: int) -> list:
    """The plan cut into device calls of `call_windows(bsz)` windows, each
    (windows, 3) int32; a window beyond the plan is dead (valid 0). A
    batch with no posting to read still makes one call."""
    size = call_windows(bsz)
    calls = []
    for lo in range(0, max(len(plan), 1), size):
        part = np.zeros((size, 3), np.int32)
        part[: len(plan[lo : lo + size])] = plan[lo : lo + size]
        calls.append(part)
    return calls


def _add_windows(total, items, weights, plan, rows_padded: int):
    """Traced: add the weights of a call's windows of postings into the
    flat (B x I_p) total — each window is `_WINDOW` consecutive postings
    from `start` (one contiguous read), its first `valid` the thing's
    own; a posting adds its weight at (batch row, item). Sums in float32,
    in the order the scatter takes them."""
    row, start, valid = plan[:, 0], plan[:, 1], plan[:, 2]
    it = jax.vmap(
        lambda s: jax.lax.dynamic_slice(items, (s,), (_WINDOW,))
    )(start)
    w = jax.vmap(
        lambda s: jax.lax.dynamic_slice(weights, (s,), (_WINDOW,))
    )(start)
    live = jnp.arange(_WINDOW, dtype=jnp.int32)[None, :] < valid[:, None]
    # a dead slot aims past the total and is dropped
    flat = jnp.where(live, row[:, None] * rows_padded + it, total.shape[0])
    return total.at[flat.reshape(-1)].add(
        jnp.where(live, w, 0.0).reshape(-1), mode="drop"
    )


@partial(jax.jit, static_argnames=("rows_padded",), donate_argnums=(0,))
def _accumulate_jit(
    total,  # (B x I_p,) float32 to add into (donated)
    items,  # the resident postings' items
    weights,  # the resident postings' weights
    plan: jax.Array,  # (W, 3) int32: batch row, start, valid
    *,
    rows_padded: int,
):
    """A call's windows added into the total and nothing else: the
    leading calls of a batch whose plan outgrows one call (long lists,
    long histories). The last call is `_score_topk_jit`'s."""
    return _add_windows(total, items, weights, plan, rows_padded)


@partial(
    jax.jit,
    static_argnames=("bsz", "windows", "rows_padded", "k", "mode", "form"),
    donate_argnums=(0,),
)
def _score_topk_jit(
    total,  # (B x I_p,) float32: zeros, or what the leading calls added
    items,  # the resident postings' items
    weights,  # the resident postings' weights
    packed,  # (windows x 3 + the exclusion's size,) int32: the call's
    # plan, then the exclusion rows / words — ONE array, one transfer
    n_items,  # () int32, TRACED, resident — live item rows (growth
    # within the pad must not recompile)
    *,
    bsz: int,
    windows: int,
    rows_padded: int,
    k: int,
    mode=None,  # resolved pallas mode for the fused tail (None = XLA)
    form: str = "none",  # the exclusion's wire form: none | rows | mask
):
    """One device program for a whole query batch: the call's windows of
    postings added into the (B, I_p) total, then the exclusion + top-k
    tail; the answer is ONE (B, 2k) int32 array (the scores' bits, then
    the item rows). A host-device round trip costs ~1.5 ms on
    a v5e's host whatever it carries (PERF.md, PR 35): a batch makes one
    transfer in, one dispatch, one copy back.

    The tail is the verb-agnostic fused kernel's precomputed-score mode
    (ISSUE 14): with `mode` set the total streams through
    `ops.recommend_pallas.fused_masked_topk` — no masked (B, I) score
    COPY, no (B, I) exclusion-mask materialization (the packed words /
    row list apply in registers). The XLA tail keeps identical semantics
    for exact mode parity."""
    from predictionio_tpu.ops import recommend_pallas as _rp

    plan = packed[: windows * 3].reshape(windows, 3)
    exclude = (
        None if form == "none"
        else packed[windows * 3 :].reshape(bsz, -1)
    )
    flat_total = _add_windows(total, items, weights, plan, rows_padded)
    total = flat_total.reshape(bsz, rows_padded)
    if mode is not None:
        vals, idx = _rp.fused_masked_topk(
            total,
            mask_bits=exclude if form == "mask" else None,
            exclude_rows=exclude if form == "rows" else None,
            k=k, n_items=n_items, interpret=(mode == "interpret"),
        )
    else:
        if form == "mask":
            total = jnp.where(
                _rp.unpack_mask_jnp(exclude, rows_padded), NEG_INF, total
            )
        elif form == "rows":
            total = jnp.where(
                _rp.rowlist_mask_jnp(exclude, rows_padded), NEG_INF, total
            )
        # dead pad columns sink strictly below the mask value, as in the
        # kernel
        col = jnp.arange(rows_padded, dtype=jnp.int32)
        total = jnp.where(
            (col >= n_items)[None, :], jnp.finfo(jnp.float32).min, total
        )
        vals, idx = jax.lax.top_k(total, k)
    # the total goes back too, never fetched: it is the donated input's
    # buffer, so the scatter adds in place
    return jnp.concatenate(
        [jax.lax.bitcast_convert_type(vals, jnp.int32), idx], axis=1
    ), flat_total


# device profiling (ISSUE 3): the UR serving hot path is one executable
# per (bucket, exclusion form). The scatter takes ten seconds to compile at
# a 4 M-row total, so neither program is compiled a second time for a
# memory analysis
_accumulate_jit = _devprof.instrument(
    "cco.accumulate_postings", _accumulate_jit
)
_score_topk_jit = _devprof.instrument("cco.score_topk", _score_topk_jit)


def batch_score_topk(
    staged: StagedCorrelators,  # `stage_correlators`, once a model
    plan: np.ndarray,  # `plan_windows(staged, histories)`: host work
    exclude: Exclusion,  # `exclusion_of` the batch's id lists
    bsz: int,  # the batch bucket: rows of the (B, I_p) total
    k: int,
    mode: str = "auto",
) -> tuple[np.ndarray, np.ndarray]:
    """Batched UR history scoring + exclusion + top-k. Returns (scores
    (B, k), item indices (B, k)); entries with score <= 0 carry no LLR
    evidence (callers filter positive-only).

    The host has planned the windows of postings the histories name
    (`plan_windows`); the device adds them into the (B, I_p) total and
    takes the top-k under the exclusion in ONE program
    (`_score_topk_jit`; a plan longer than `call_windows` makes leading
    `_accumulate_jit` calls first). `mode` gates the fused tail
    (resolve_mode contract: "auto" → tpu where the lowering runs /
    "interpret" for tests / None|"off" → the XLA tail). The exclusion
    ships in the form its ids call for (`exclusion_of`): nothing, a row
    list, or — beyond `ROWLIST_MAX` ids a query — packed words (1/32
    the f32-equivalent mask bytes over the wire and in HBM)."""
    from predictionio_tpu.ops import recommend_pallas as _rp

    # the total starts as zeros made on the device (no round trip); all
    # but the last call of the batch's plan only add into it. Making it
    # and handing over the batch's one packed input is a span of its own
    # (ISSUE 37): what the host does for the device before the call
    with _spans.span("ur.predict.put"):
        total = jnp.zeros((bsz * staged.rows_padded,), jnp.float32)
        *leading, last = plan_calls(plan, bsz)
        packed = last.reshape(-1)
        if exclude.array is not None:
            packed = np.concatenate([packed, exclude.array.reshape(-1)])
        packed = jnp.asarray(packed)
    for part in leading:
        total = _accumulate_jit(
            total, staged.items, staged.weights, jnp.asarray(part),
            rows_padded=staged.rows_padded,
        )
    out, _total = _score_topk_jit(
        total, staged.items, staged.weights, packed,
        staged.n_items_device,
        bsz=bsz, windows=len(last), rows_padded=staged.rows_padded,
        k=k, mode=_rp.resolve_mode(mode), form=exclude.form,
    )
    # the one copy back, a span of its own (ISSUE 37): the profiler's
    # wrapper has blocked on the program, this is the round trip alone
    with _spans.span("ur.predict.copy_back"):
        out = np.asarray(out)
    return out[:, :k].view(np.float32), out[:, k:]
