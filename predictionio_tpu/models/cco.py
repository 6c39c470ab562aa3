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

import contextvars
import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from predictionio_tpu.obs import devprof as _devprof
from predictionio_tpu.obs import get_default_registry
from predictionio_tpu.obs import spans as _spans
from predictionio_tpu.ops.topk import NEG_INF, masked_top_k


def _log(x):
    """log(x) of float32 x > 0 from multiplies, adds and one divide: x =
    m·2^k with m in [√½, √2), log m = 2·atanh(s), s = (m − 1)/(m + 1),
    |s| ≤ 0.172, by its series to s¹¹ (error < s¹³/13 ≈ 10⁻¹¹), and k·ln 2
    in two parts: ~2·10⁻⁷ relative, the same on any backend. The LLR's
    earlier form, with the backend's log1p, read up to 7.8·10⁻⁵ on the
    v5e against 6·10⁻⁷ on a CPU (PERF.md, PR 39)."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    k = (bits >> 23) - 127
    m = jax.lax.bitcast_convert_type(
        (bits & 0x7FFFFF) | 0x3F800000, jnp.float32)
    big = m > 1.4142135
    m = jnp.where(big, 0.5 * m, m)
    k = (k + big).astype(jnp.float32)
    s = (m - 1.0) / (m + 1.0)
    s2 = s * s
    series = 2.0 * s * (1.0 + s2 * (1 / 3 + s2 * (1 / 5 + s2 * (
        1 / 7 + s2 * (1 / 9 + s2 / 11)))))
    return k * 0.693145751953125 + (k * 1.428606765330187e-06 + series)


def _kl_cell(k, e, delta):
    """E·φ(u), φ(u) = (1 + u)·log(1 + u) − u ≥ 0, for a cell of count k,
    expectation e and deviation delta = k − e (u = delta / e): the cell's
    share of the LLR, k·log(k/E) − (k − E). Near u = 0 the direct form
    cancels, so there its series u²/2 − u³/6 + u⁴/12 − u⁵/20 + u⁶/30 −
    u⁷/42 is taken (error under u⁸/56: < 10⁻⁹ of φ at |u| ≤ 0.1); an
    empty cell adds E (φ(−1) = 1). The log is `_log`, of k/E itself."""
    e = jnp.where(e > 0, e, 1.0)
    u = delta / e
    small = jnp.abs(u) < 0.1
    us = jnp.where(small, u, 0.0)
    series = us * us * (0.5 + us * (-1 / 6 + us * (1 / 12 + us * (
        -1 / 20 + us * (1 / 30 + us * (-1 / 42))))))
    ratio = jnp.where(small | (k <= 0), 1.0, k / e)
    direct = jnp.where(k > 0, ratio * _log(ratio), 0.0) - u
    return e * jnp.where(small, series, direct)


def llr(k11, r, c, n):
    """Dunning's log-likelihood ratio of the 2×2 contingency table of a
    pair, elementwise: k11 users did both, r the first, c the second, of n.

    Written as 2·Σ E·φ(δ/E) over the four cells, E a cell's expectation
    r·c/n, r·(n − c)/n, (n − r)·c/n, (n − r)·(n − c)/n and δ = ±d/n its
    deviation, d = k11·n − r·c: the same sum as 2·Σ k·log(k/E) (the
    deviations sum to 0), but of four terms ≥ 0, each computed where it
    does not cancel (`_kl_cell`), with a log of multiplies and adds
    (`_log`). The entropy form (Σ x·log x of cells,
    rows, columns and n) takes differences of terms of ~1.4·10⁷ at
    n ≈ 10⁶ and loses about one unit in float32; 2·Σ k·log(k/E) with
    log1p for the three cells beside k11 read up to 7.8·10⁻⁵ relative on
    the v5e, against ~6·10⁻⁷ on a CPU (PERF.md, PR 39). A table with d
    exactly 0 — counts under 2²⁴, as float32 holds them — reads exactly
    0, any other table is positive, so `> 0` tells a correlator from an
    independent pair whatever the rounding. k11 = 0 reads 0: a pair that
    never co-occurs is no correlator."""
    f32 = jnp.float32
    k11, r, c = (jnp.asarray(x, f32) for x in (k11, r, c))
    n = jnp.asarray(n, f32)
    live = (k11 > 0) & (r > 0) & (c > 0)
    # the guards keep every value finite where a table is dead (it is
    # masked to 0 below), so no NaN reaches a sort or a top-k
    r, c = jnp.where(live, r, 1.0), jnp.where(live, c, 1.0)
    pr, pc = r / n, c / n
    e11 = r * pc  # r·c / n
    e = k11 - e11  # d / n
    e12, e21 = r * (1.0 - pc), (1.0 - pr) * c
    e22 = n * (1.0 - pr) * (1.0 - pc)
    out = 2.0 * (
        _kl_cell(k11, e11, e)
        + _kl_cell(r - k11, e12, -e)
        + _kl_cell(c - k11, e21, -e)
        + _kl_cell(n - r - c + k11, e22, e)
    )
    # d = 0 exactly: d mod 2³² in int32 (wrapping) is 0 and the float
    # estimate of |d| is far under 2³¹, which it misses by < 2²⁶
    i32 = jnp.int32
    d_wrapped = k11.astype(i32) * n.astype(i32) - r.astype(i32) * c.astype(i32)
    independent = (d_wrapped == 0) & (jnp.abs(e * n) < 2.0**30)
    return jnp.where(
        live & ~independent, jnp.maximum(out, jnp.finfo(f32).tiny), 0.0
    )


def llr_scores(
    k11: jax.Array,  # (I, J) co-occurrence counts
    prim_totals: jax.Array,  # (I,) per-item event totals
    sec_totals: jax.Array,  # (J,) per-thing event totals
    n_users: jax.Array | float,
) -> jax.Array:
    """Dunning LLR (`llr`) of the 2×2 contingency per pair of a dense
    count matrix."""
    return llr(k11, prim_totals[:, None], sec_totals[None, :], n_users)


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
    scores = llr_scores(counts, prim_totals, sec_totals, n_users)
    exclude = scores <= 0  # never-co-occurring or independent pairs
    if exclude_diagonal:
        # the diagonal of the GLOBAL (I, I) matrix: global row index =
        # diag_offset + local row (item blocking shifts the block)
        r = jnp.arange(scores.shape[0], dtype=jnp.int32)[:, None] + diag_offset
        c = jnp.arange(scores.shape[1], dtype=jnp.int32)[None, :]
        exclude = exclude | (r == c)
    vals, idx = masked_top_k(scores, top_n, exclude)
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


# ---------------------------------------------------------------------------
# The train path from sparse pairs (ISSUE 39)
# ---------------------------------------------------------------------------
#
# The dense product above builds a (users × things) matrix a side: 16 TB
# at Taobao UserBehavior's 988 k users × 4.16 M items, where the events
# are 100 M pairs and the co-occurrence counts are ~5·10⁻⁵ dense. So a job
# works on sorted pairs: each indicator's events binarised and grouped by
# user on the host (one sort of packed keys, as `models/als.py`
# `_group_unique_pairs` does), Mahout's downsampling drawn from a stated
# hash, then every user's primary × secondary pairs, indicator after
# indicator, cut by item range into blocks of `BLOCK_PAIRS`: for each
# block the host lays the pairs out and ONE device program sorts them by
# (item, thing) and counts each run, ONE scores the runs by LLR and
# places each item's best `top_n` into the resident tables of every
# indicator, in the (I, top_n) layout `IndicatorModel` and
# `ResidentCorrelators` take.
#
# Why this split (PERF.md, PR 39, probes on the v5e): the chip sorts
# ~5 ns a pair but GATHERS ~7.5 ns an element from a 1.9 M-entry table and
# ~47 from an 86 M-entry one, so the pairs are laid out on the host (a
# fill and a cumulative sum over reused buffers) with the LLR's margins
# riding along; a sort compiles in ~40 s whatever its length, so every
# block has ONE shape and two programs serve every job and indicator.

#: the downsampling draw's seed: Mahout's `cooccurrences` default
#: `randomSeed`
DOWNSAMPLE_SEED = 0xDEADBEEF

#: pairs a block holds at most. A job with more cuts its pairs into
#: blocks of this one shape, so each program compiles once; a smaller job
#: takes one block of its own size (`_bucket`). An item's pairs never
#: straddle two blocks.
#:
#: The host lays the blocks out one ahead of the device, into two sets of
#: buffers used in turn: while the caller's thread runs block n's two
#: programs, block n + 1 is laid out into the other set and handed over
#: (`_Pipeline`). A block's layout is cut into chunks of contiguous
#: primary events, each writing its own span of the buffers, and the
#: chunks run on `_LAYOUT_WORKERS` threads. A set is written again only
#: once the program that read it has run, and block n + 1 is handed over
#: only once block n's `_pair_counts_jit` has returned: at most one
#: block's inputs wait on the device
BLOCK_PAIRS = 1 << 25

#: threads that lay a block's chunks out: the CPUs this process may run
#: on, at most 8
_LAYOUT_WORKERS = max(1, min(8, (
    len(os.sched_getaffinity(0)) if hasattr(os, "sched_getaffinity")
    else os.cpu_count() or 1)))

#: pairs a layout chunk holds, as near as whole primary events allow: a
#: block of fewer is one chunk
_LAYOUT_CHUNK_PAIRS = 1 << 21

#: sort key of a dead pair (padding, the diagonal, a pair not kept): after
#: every item and thing
_DEAD = np.iinfo(np.int32).max

_M32 = np.uint64(0xFFFFFFFF)


def _fmix32(h: np.ndarray) -> np.ndarray:
    """murmur3's 32-bit finaliser on uint64 holding 32-bit values, in
    place for an array."""
    h = np.array(h, np.uint64, ndmin=1)
    tmp = np.empty_like(h)
    for shift, mul in ((16, 0x85EBCA6B), (13, 0xC2B2AE35), (16, None)):
        np.right_shift(h, np.uint64(shift), out=tmp)
        np.bitwise_xor(h, tmp, out=h)
        if mul is not None:
            np.multiply(h, np.uint64(mul), out=h)
            np.bitwise_and(h, _M32, out=h)
    return h


def sample_draw(seed: int, indicator: int, rows: np.ndarray,
                cols: np.ndarray) -> np.ndarray:
    """The downsampling's draw for each (user row, item row) of an
    indicator, a uint64 holding a 32-bit value: with fmix32 murmur3's
    finaliser and seed = hi·2³² + lo (mod 2⁶⁴), salt =
    fmix32(fmix32(fmix32(indicator) ^ hi) ^ lo), draw =
    fmix32(fmix32(salt ^ row) ^ col). Counter-based: a job is repeatable
    and a reference redoes it from this text."""
    seed = int(seed) % 2**64
    salt = _fmix32(np.uint64(indicator))
    salt = _fmix32(salt ^ np.uint64(seed >> 32))
    salt = _fmix32(salt ^ np.uint64(seed & 0xFFFFFFFF))
    h = _fmix32(np.asarray(rows, np.uint64) ^ salt)
    h ^= np.asarray(cols, np.uint64)
    return _fmix32(h)


class UserEvents(NamedTuple):
    """One indicator's distinct (user, thing) events grouped by user: user
    u's things are `cols[ptr[u]:ptr[u + 1]]`, ascending."""

    cols: np.ndarray  # (n,) int32
    ptr: np.ndarray  # (n_users + 1,) int64
    n_cols: int

    def rows(self) -> np.ndarray:
        return np.repeat(
            np.arange(len(self.ptr) - 1, dtype=np.int32), np.diff(self.ptr))

    def totals(self) -> np.ndarray:
        """Events a thing (int32; float32 holds them exactly below 2²⁴)."""
        return np.bincount(self.cols, minlength=self.n_cols).astype(np.int32)


def group_by_user(rows: np.ndarray, cols: np.ndarray, n_users: int,
                  n_cols: int) -> UserEvents:
    """Binarise an indicator's events (a pair seen twice counts once) and
    group them by user: one in-place sort of int64 keys user << b | col
    (2^b ≥ n_cols), neighbours compared, the users' starts found by
    binary search."""
    bits = max(int(n_cols - 1).bit_length(), 1)
    key = np.left_shift(np.asarray(rows, np.int64), bits)
    key |= np.asarray(cols, np.int64)
    key.sort()
    if key.size:
        fresh = np.empty(key.size, bool)
        fresh[0] = True
        np.not_equal(key[1:], key[:-1], out=fresh[1:])
        key = key[fresh]
    ptr = np.searchsorted(
        key, np.left_shift(np.arange(n_users + 1, dtype=np.int64), bits))
    key &= (1 << bits) - 1
    return UserEvents(key.astype(np.int32), ptr, int(n_cols))


def downsample(events: UserEvents, cap: int, seed: int,
               indicator: int) -> UserEvents:
    """Mahout's `sampleDownAndBinarize` (`maxNumInteractions` = `cap`):
    each distinct (user, thing) is kept with probability
    min(1, cap / n_user, cap / n_thing), the counts those of the distinct
    events before the draw; it is kept iff draw · max(n_user, n_thing) <
    cap · 2³², in integers (`sample_draw`). The draw is evaluated only
    where a count is over the cap: elsewhere the pair is always kept."""
    n_user = np.diff(events.ptr)
    n_thing = np.bincount(events.cols, minlength=events.n_cols)
    over_u = n_user > cap
    over_t = n_thing > cap
    if not (over_u.any() or over_t.any()):
        return events
    binding = over_t[events.cols]
    binding |= np.repeat(over_u, n_user)
    binding = np.flatnonzero(binding)
    r = np.searchsorted(events.ptr, binding, side="right") - 1
    c = events.cols[binding]
    most = np.maximum(n_user[r], n_thing[c]).astype(np.uint64)
    drop = sample_draw(seed, indicator, r, c)
    drop *= most
    drop = drop >= np.uint64(cap) << np.uint64(32)
    keep = np.ones(events.cols.size, bool)
    keep[binding[drop]] = False
    dropped = np.bincount(r[drop], minlength=n_user.size)
    ptr = events.ptr.copy()
    ptr[1:] -= np.cumsum(dropped)
    return UserEvents(events.cols[keep], ptr, events.n_cols)


def _bucket(n: int) -> int:
    """A padded length for `n` entries: n rounded up to a multiple of
    2^(bits(n) − 4), at most 1/8 more."""
    quantum = 1 << max(10, int(n).bit_length() - 4)
    return max(quantum, -(-int(n) // quantum) * quantum)


def _plan_blocks(pairs_per_item: np.ndarray, size: int) -> list:
    """Cut the axis of (indicator, item) — g = m·I + item — into blocks of
    at most `size` pairs: [(g_lo, g_hi), ...], an item whole in one
    block."""
    cum = np.concatenate([[0], np.cumsum(pairs_per_item, dtype=np.int64)])
    blocks, lo = [], 0
    while lo < pairs_per_item.size:
        hi = int(np.searchsorted(cum, cum[lo] + size, side="right")) - 1
        if hi <= lo:
            raise ValueError(
                f"an item makes {int(pairs_per_item[lo])} pairs: more than "
                f"a block of {size}")
        blocks.append((lo, hi))
        lo = hi
    return blocks


class _PairBlock:
    """One set of the host's buffers of a block, reused every other
    block: each pair's key g = m·I + item, its thing, and the LLR's
    margins (the item's and the thing's kept events). Two sets may share
    one read-only `arange`."""

    def __init__(self, size: int, arange: Optional[np.ndarray] = None):
        self.items = np.empty(size, np.int32)
        self.things = np.empty(size, np.int32)
        self.prim_totals = np.empty(size, np.int32)
        self.sec_totals = np.empty(size, np.int32)
        self.at = np.empty(size, np.int32)
        self.arange = (np.arange(size, dtype=np.int32) if arange is None
                       else arange)

    @staticmethod
    def _fill(buf, starts, values):
        """`buf` constant `values[j]` from `starts[j]` to the next start:
        the differences at the starts, then one cumulative sum."""
        buf.fill(0)
        buf[starts] = np.diff(values, prepend=0)
        np.cumsum(buf, out=buf)

    def lay_out(self, at: int, items, prim_totals, firsts, lens,
                secondary: UserEvents, sec_totals) -> None:
        """Write the pairs of primary events (their keys, their items'
        totals, their users' first secondary event, the number of those
        events) from `at`: each event's key repeated over its user's
        secondary events, which are the things."""
        end = at + int(lens.sum())
        starts = np.cumsum(lens) - lens
        span = slice(at, end)
        self._fill(self.items[span], starts, items)
        self._fill(self.prim_totals[span], starts, prim_totals)
        # the position of the pair's thing among the secondary events:
        # its user's first, plus how far into the event's run it lies
        self._fill(self.at[span], starts, firsts - starts)
        self.at[span] += self.arange[: end - at]
        np.take(secondary.cols, self.at[span], out=self.things[span])
        np.take(sec_totals, self.things[span], out=self.sec_totals[span])


class _Layout:
    """What laying a block out reads, for every block of a join: the
    primary's kept events in item order (their items, their users and,
    an indicator each, how many secondary events their user has), the
    items' totals, and every indicator's events and totals."""

    def __init__(self, primary: UserEvents, secondaries: list):
        order = np.argsort(primary.cols, kind="stable")
        self.by_item = primary.cols[order]
        self.users = primary.rows()[order]
        self.prim_totals = primary.totals()
        self.secondaries = secondaries
        self.sec_totals = [sec.totals() for sec in secondaries]
        self.lens = [np.diff(sec.ptr)[self.users] for sec in secondaries]
        self.n_items = primary.n_cols

    def chunks(self, g_lo: int, g_hi: int, limit: int) -> tuple[list, dict]:
        """The block [g_lo, g_hi) cut into chunks of about `limit` pairs:
        [(m, first event, end event, at), ...], each a run of the primary
        events of indicator m's item range in the block, whose pairs are
        written from `at`; and the block's indicators and pairs."""
        chunks, at, indicators = [], 0, []
        n = self.n_items
        for m in range(g_lo // n, (g_hi - 1) // n + 1):
            e_lo, e_hi = (int(e) for e in np.searchsorted(
                self.by_item, [max(g_lo - m * n, 0), min(g_hi - m * n, n)]))
            # the pairs up to each event's end; a chunk ends at the last
            # event that ends within its multiple of `limit`
            ends = np.cumsum(self.lens[m][e_lo:e_hi])
            pairs = int(ends[-1]) if ends.size else 0
            cuts = np.searchsorted(ends, np.arange(limit, pairs, limit),
                                   side="right")
            bounds = np.unique(np.concatenate([[0], cuts, [e_hi - e_lo]]))
            for a, b in zip(bounds[:-1].tolist(), bounds[1:].tolist()):
                chunks.append(
                    (m, e_lo + a, e_lo + b, at + (int(ends[a - 1]) if a else 0)))
            indicators.append(m)
            at += pairs
        return chunks, {"indicators": indicators, "pairs": at}

    def lay_out(self, buf: _PairBlock, chunk: tuple) -> None:
        """One chunk's pairs into its span of `buf`."""
        m, e_lo, e_hi, at = chunk
        lens = self.lens[m][e_lo:e_hi]
        live = lens > 0
        items = self.by_item[e_lo:e_hi][live]
        sec = self.secondaries[m]
        buf.lay_out(at, items + m * self.n_items, self.prim_totals[items],
                    sec.ptr[self.users[e_lo:e_hi][live]], lens[live], sec,
                    self.sec_totals[m])


def _hand_over(buf: _PairBlock) -> tuple:
    """A laid-out block's four arrays on the device, transferred."""
    return jax.block_until_ready(tuple(jax.device_put(a) for a in (
        buf.items, buf.things, buf.prim_totals, buf.sec_totals)))


_LAYOUT_BLOCKS = get_default_registry().counter(
    "ur_train_layout_blocks_total",
    "join blocks by whether their layout was on the device when the join "
    "asked for it (ahead) or set the pace (waited; a job's first block)",
    labelnames=("ready",),  # label-bound: literal set ahead / waited
)


class _Pipeline:
    """A join's blocks laid out and handed over one ahead of the caller,
    on a coordinating thread and `_LAYOUT_WORKERS` chunk threads: block k
    goes into buffer set k mod 2, so block k + 2 is laid out only once
    the caller has `consumed` block k, and block k + 1 is handed over only
    then too. A context manager: on exit, by error too, the threads are
    stopped and joined."""

    def __init__(self, layout: _Layout, blocks: list, size: int):
        self._layout, self._blocks = layout, blocks
        first = _PairBlock(size)
        self._sets = (first, _PairBlock(size, first.arange))
        self._consumed = [threading.Event() for _ in blocks]
        self._closed = False
        self._chunk_pool = ThreadPoolExecutor(
            _LAYOUT_WORKERS, thread_name_prefix="ur-layout")
        self._coordinator = ThreadPoolExecutor(
            1, thread_name_prefix="ur-layout-hand-over")
        self._jobs: dict = {}
        for k in range(min(2, len(blocks))):
            self._submit(k)

    def __enter__(self) -> "_Pipeline":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def _submit(self, k: int) -> None:
        # in a copy of the caller's context: the span counts in the job's
        # collected timings
        self._jobs[k] = self._coordinator.submit(
            contextvars.copy_context().run, self._prepare, k)

    def _prepare(self, k: int):
        """Block k laid out into its set and handed over: (its indicators
        and pairs, the four device arrays)."""
        buf = self._sets[k % 2]
        # a root of its own: the caller's spans keep their self time. It
        # times the layout alone, not the wait for the device to take it
        with _spans.detached(), _spans.span(
                "ur.train.lay_out", workers=_LAYOUT_WORKERS) as sp:
            chunks, block = self._layout.chunks(
                *self._blocks[k], _LAYOUT_CHUNK_PAIRS)
            sp.attrs.update(chunks=len(chunks), pairs=block["pairs"])
            for done in [self._chunk_pool.submit(self._layout.lay_out, buf, c)
                         for c in chunks]:
                done.result()
        if k:
            self._consumed[k - 1].wait()
        if self._closed:
            return None
        return block, _hand_over(buf)

    def ready(self, k: int):
        """Block k's (indicators and pairs, device arrays), once handed
        over; a layout's error is raised here."""
        return self._jobs.pop(k).result()

    def consumed(self, k: int) -> None:
        """Block k's program has run: its set is free, and block k + 1
        may go to the device."""
        self._consumed[k].set()
        if k + 2 < len(self._blocks):
            self._submit(k + 2)

    def close(self) -> None:
        self._closed = True
        for event in self._consumed:
            event.set()
        self._coordinator.shutdown(wait=True, cancel_futures=True)
        self._chunk_pool.shutdown(wait=True, cancel_futures=True)


@jax.jit
def _pair_counts_jit(  # lint: disable=jit-boundary — train, once a block
    items,  # (B,) int32: each pair's key g = m·I + item (padding: any)
    things,  # (B,) int32: its secondary thing
    prim_totals,  # (B,) int32: the item's kept events
    sec_totals,  # (B,) int32: the thing's kept events
    n_pairs,  # () int32: live pairs; the rest is padding
    diagonal_below,  # () int32: keys under it are the self indicator's
):
    """The pairs sorted by (key, thing), the totals riding along, and
    each run of equal pairs counted: at the run's first pair its length,
    0 elsewhere; and the number of runs. A self-indicator pair of an item
    with itself is dropped; a dead pair sorts last under `_DEAD`."""
    p = jnp.arange(items.shape[0], dtype=jnp.int32)
    live = (p < n_pairs) & ~((things == items) & (items < diagonal_below))
    items, things, prim_totals, sec_totals = jax.lax.sort(
        (jnp.where(live, items, _DEAD), jnp.where(live, things, _DEAD),
         prim_totals, sec_totals),
        num_keys=2,
    )
    dead = items == _DEAD
    first = jnp.concatenate([
        jnp.ones((1,), bool),
        (items[1:] != items[:-1]) | (things[1:] != things[:-1]),
    ]) & ~dead
    # a run's length: where the next run (or the dead tail) starts, less
    # its own start
    nxt = jax.lax.cummin(jnp.where(first | dead, p, p.shape[0]), reverse=True)
    nxt = jnp.concatenate([nxt[1:], jnp.full((1,), p.shape[0], jnp.int32)])
    counts = jnp.where(first, nxt - p, 0)
    return (items, things, counts, prim_totals, sec_totals,
            jnp.sum(first, dtype=jnp.int32))


@partial(jax.jit, static_argnames=("top_n",), donate_argnums=(0, 1))
def _llr_topn_jit(  # lint: disable=jit-boundary — train, once a block
    table_idx,  # (M·I·top_n,) int32, every indicator's (donated)
    table_scores,  # (M·I·top_n,) float32 (donated)
    items,  # (B,) int32 sorted keys, `_DEAD` past the live pairs
    things,  # (B,) int32
    counts,  # (B,) int32: a run's length at its first pair, else 0
    prim_totals,  # (B,) int32
    sec_totals,  # (B,) int32
    n_users,  # () float32
    *,
    top_n: int,
):
    """Each run's LLR, then each key's best `top_n` things: the runs
    sorted by (key, −LLR, thing), a run's rank in its key's segment, and
    the first `top_n` of a segment scattered into the key's row of the
    tables, in place; a row keeps -1 / 0 where it has fewer."""
    score = llr(counts, prim_totals, sec_totals, n_users)
    keep = score > 0
    items, neg, things = jax.lax.sort(
        (jnp.where(keep, items, _DEAD), jnp.where(keep, -score, 0.0),
         jnp.where(keep, things, _DEAD)),
        num_keys=3,
    )
    p = jnp.arange(items.shape[0], dtype=jnp.int32)
    first = jnp.concatenate([jnp.ones((1,), bool), items[1:] != items[:-1]])
    rank = p - jax.lax.cummax(jnp.where(first, p, 0))
    slot = jnp.where((rank < top_n) & (items != _DEAD),
                     items * top_n + rank, table_idx.shape[0])
    return (table_idx.at[slot].set(things, mode="drop"),
            table_scores.at[slot].set(-neg, mode="drop"))


@partial(jax.jit, static_argnames=("rows", "top_n"))
def _table_rows(table_idx, table_scores, start, *, rows: int, top_n: int):  # lint: disable=jit-boundary — train, once an indicator
    """One indicator's (rows, top_n) tables out of every indicator's."""
    size = rows * top_n
    return (
        jax.lax.dynamic_slice_in_dim(table_idx, start, size).reshape(
            rows, top_n),
        jax.lax.dynamic_slice_in_dim(table_scores, start, size).reshape(
            rows, top_n),
    )


_pair_counts_jit = _devprof.instrument("cco.pair_counts", _pair_counts_jit)
_llr_topn_jit = _devprof.instrument("cco.llr_topn", _llr_topn_jit)


def join_indicators(
    primary: UserEvents,
    secondaries: list,
    n_users: int,
    top_n: int,
    self_first: bool = False,
) -> tuple[list, dict]:
    """[(scores (I, top_n), idx (I, top_n) -1 padded), ...] one an
    indicator of `secondaries` (each `UserEvents` over its own things),
    joined with the primary's kept events, and the stats: the pairs of
    each indicator, and each block's indicators, pairs and distinct. With
    `self_first`, `secondaries[0]` is the primary itself and an item is
    not its own correlator.

    The pairs of every indicator, item range by item range, fill blocks
    of at most `BLOCK_PAIRS` (`_plan_blocks`). A block is laid out on the
    host one ahead of the device (`_Pipeline`: two buffer sets used in
    turn, a block in chunks on worker threads; span `ur.train.lay_out`
    a block, attrs `chunks`, `workers`, `pairs`). On the caller's thread
    a block is waited for and joined on the device (span `ur.train.join`
    a block: attrs `indicators`, `pairs`, `distinct`, `ready_wait_s`;
    counter `ur_train_layout_blocks_total{ready}`), then scored and
    placed (`ur.train.llr_topn`). An indicator's tables start home as
    soon as its last block is placed; `ur.train.copy_back` waits for
    what is left."""
    n_items = primary.n_cols
    n_ind = len(secondaries)
    if n_ind * n_items * top_n >= _DEAD:
        raise ValueError("the tables' slots outnumber an int32")
    layout = _Layout(primary, secondaries)
    per_item = np.zeros((n_ind, n_items), np.int64)
    for m, lens in enumerate(layout.lens):
        per_item[m] = np.bincount(layout.by_item, weights=lens,
                                  minlength=n_items).astype(np.int64)
    size = min(BLOCK_PAIRS, _bucket(max(int(per_item.sum()), 1)))
    blocks = _plan_blocks(per_item.reshape(-1), size)
    table_idx = jnp.full((n_ind * n_items * top_n,), -1, jnp.int32)
    table_scores = jnp.zeros((n_ind * n_items * top_n,), jnp.float32)
    homeward, tables, stats = [], [], []
    with _Pipeline(layout, blocks, size) as ahead:
        for k, (_g_lo, g_hi) in enumerate(blocks):
            with _spans.span("ur.train.join") as sp:
                t0 = time.monotonic()
                block, inputs = ahead.ready(k)
                waited = time.monotonic() - t0
                _LAYOUT_BLOCKS.inc(
                    ready="ahead" if k and waited < 1e-3 else "waited")
                *pairs, distinct = _pair_counts_jit(
                    *inputs,
                    jnp.int32(block["pairs"]),
                    jnp.int32(n_items if self_first else 0),
                )
                del inputs
                block["distinct"] = int(distinct)  # the set is free again
                ahead.consumed(k)
                sp.attrs.update(block, ready_wait_s=waited)
            stats.append(block)
            # an indicator whose last item this block placed starts home
            # while the next block is joined
            for pending in homeward:
                tables.append(tuple(np.asarray(a) for a in pending))
            homeward = []
            with _spans.span("ur.train.llr_topn"):
                table_idx, table_scores = _llr_topn_jit(
                    table_idx, table_scores, *pairs, jnp.float32(n_users),
                    top_n=top_n)
                del pairs
                jax.block_until_ready(table_scores)
            while len(tables) + len(homeward) < n_ind and (
                    len(tables) + len(homeward) + 1) * n_items <= g_hi:
                m = len(tables) + len(homeward)
                idx, scores = _table_rows(
                    table_idx, table_scores, jnp.int32(m * n_items * top_n),
                    rows=n_items, top_n=top_n)
                idx.copy_to_host_async()
                scores.copy_to_host_async()
                homeward.append((scores, idx))
    with _spans.span("ur.train.copy_back"):
        for pending in homeward:
            tables.append(tuple(np.asarray(a) for a in pending))
    return tables, {"pairs": [int(n.sum()) for n in layout.lens],
                    "blocks": stats}


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
