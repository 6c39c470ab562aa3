"""Windowed (scatter-free) segment reduction for sorted edge lists.

The ALS normal-equation builders reduce 20M+ per-edge contributions into
per-row sums. XLA's scatter-add on TPU serializes per row (~9 ns/edge
measured on v5e — 174 ms for one 20M-edge scalar segment-sum), which made
the scatter-based gram/b builders the dominant cost of an ALS half-step
(~555 ms/pass at the ML-20M north star).

This module replaces the scatter with MXU matmuls (measured ~18× faster
at the same scale):

1. HOST PLAN (once per training set): cut the dst-sorted edge list into
   blocks of ≤ `block_edges` edges that never cross an `S`-row aligned
   output window. Blocks are padded to a fixed length; ≤ 3% inflation at
   MovieLens-20M degree distributions (one short block per non-empty
   window).
2. DEVICE PASS: for each block, build the (block_edges, S) one-hot of
   local row ids and contract it against the per-edge payload on the MXU
   — a batched (S × block_edges) @ (block_edges × D) matmul — giving
   per-block partial sums (n_blocks, S, D).
3. COMBINE: one segment-sum over the ~E/block_edges block rows (three
   orders of magnitude fewer scatter rows than edges).

The payload D packs the ALS b-vector (K lanes) and the flattened gram
correction (K² lanes) built from ONE factor gather, so a full implicit
half-step needs a single edge pass.

Role in the reference: this is the TPU replacement for MLlib ALS's
block-partitioned shuffle aggregation (org.apache.spark.mllib ALS used by
examples/scala-parallel-recommendation/*/ALSAlgorithm.scala:50-57).
"""

from __future__ import annotations
from predictionio_tpu.utils.env import env_str as _env_str

import os
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

# Output window rows. 128 = one lane-width of rows; windows are aligned so
# every edge's local row id is dst % S with no per-edge host work.
WINDOW_ROWS = 128
# Max edges per block — the one-hot matmul's contraction length.
BLOCK_EDGES = 1024
# Blocks per scan step: bounds live intermediates to
# CHUNK_BLOCKS * BLOCK_EDGES * 128 lanes * 4 B ≈ 67 MB per materialized
# tensor (gather, one-hot, payload).
CHUNK_BLOCKS = 128


@dataclass(frozen=True)
class WindowPlan:
    """Host-side blocking of one dst-sorted edge list.

    The plan re-indexes every per-edge array through `edge_index` (padding
    slots point at edge 0 with valid=0), reshaped to (n_parts,
    chunks_per_part, chunk_blocks, block_edges). `n_parts` > 1 splits the
    block list into contiguous per-device groups for data-parallel
    training: axis 0 shards over the mesh's dp axis, and because blocks
    (hence output windows) are assigned to parts contiguously, the
    part-major global block order keeps window ids non-decreasing —
    padding blocks inside a part carry the part's LAST real window id
    (zero-weight, so they contribute nothing) to preserve sortedness.
    """

    edge_index: np.ndarray  # (E_p,) int — padded slot → original edge
    valid: np.ndarray  # (E_p,) float32 — 0.0 on padding slots
    local: np.ndarray  # (E_p,) int32 — dst % S per slot
    block_window: np.ndarray  # (n_blocks_p,) int32 — output window per block
    n_blocks: int  # real blocks (before padding)
    n_blocks_p: int  # padded blocks: n_parts * chunks_per_part * CB
    n_chunks: int  # n_parts * chunks_per_part
    n_windows: int  # output rows padded to n_windows * S
    n_rows: int  # true output row count
    n_parts: int = 1
    chunks_per_part: int = 1

    @property
    def n_rows_padded(self) -> int:
        return self.n_windows * WINDOW_ROWS

    def _shape4(self):
        return (self.n_parts, self.chunks_per_part, CHUNK_BLOCKS, BLOCK_EDGES)

    def take(self, per_edge: np.ndarray) -> np.ndarray:
        """Re-index a per-edge array into padded (P, L, CB, B_E) form.
        Float arrays are masked by `valid` so padding slots are inert."""
        if per_edge.size == 0:  # empty training set: all-padding plan
            per_edge = np.zeros(1, per_edge.dtype)
        out = per_edge[self.edge_index]
        if np.issubdtype(out.dtype, np.floating):
            out = out * self.valid
        return out.reshape(self._shape4())

    def chunked_local(self) -> np.ndarray:
        return self.local.reshape(self._shape4())

    def chunked_valid(self) -> np.ndarray:
        return self.valid.reshape(self._shape4())


def plan_windows(
    dst_sorted: np.ndarray, n_rows: int, n_parts: int = 1
) -> WindowPlan:
    """Build the block/window plan for a dst-sorted edge list. O(E) numpy.

    `n_parts` > 1 splits blocks into that many contiguous equal-size
    (padded) groups — one per data-parallel device."""
    S, B_E, CB = WINDOW_ROWS, BLOCK_EDGES, CHUNK_BLOCKS
    dst_sorted = np.asarray(dst_sorted)
    n_windows = max(1, -(-n_rows // S))
    if dst_sorted.size == 0:  # no edges: all-padding plan
        return WindowPlan(
            edge_index=np.zeros(n_parts * CB * B_E, np.int64),
            valid=np.zeros(n_parts * CB * B_E, np.float32),
            local=np.zeros(n_parts * CB * B_E, np.int32),
            block_window=np.zeros(n_parts * CB, np.int32),
            n_blocks=1,
            n_blocks_p=n_parts * CB,
            n_chunks=n_parts,
            n_windows=n_windows,
            n_rows=n_rows,
            n_parts=n_parts,
            chunks_per_part=1,
        )
    win = dst_sorted // S
    cnt = np.bincount(win, minlength=n_windows).astype(np.int64)
    nb_per_win = -(-cnt // B_E)
    nb_per_win[cnt == 0] = 0
    n_blocks = int(nb_per_win.sum())
    block_win = np.repeat(
        np.arange(n_windows, dtype=np.int32), nb_per_win
    )
    blk_in_win = np.concatenate(
        [np.arange(k, dtype=np.int64) for k in nb_per_win if k > 0]
    )
    rem = cnt[block_win] - blk_in_win * B_E
    block_len = np.clip(rem, 0, B_E).astype(np.int64)
    win_start = np.zeros(n_windows + 1, np.int64)
    np.cumsum(cnt, out=win_start[1:])
    block_start = win_start[block_win] + blk_in_win * B_E

    # contiguous equal-count split of real blocks over parts, each part
    # padded to a common chunk multiple (SPMD: every device scans the
    # same number of chunks)
    bounds = np.linspace(0, n_blocks, n_parts + 1).astype(np.int64)
    sizes = np.diff(bounds)
    L = max(1, int(-(-sizes.max() // CB)))
    bpp = L * CB  # padded blocks per part
    n_blocks_p = n_parts * bpp

    # padded-slot → real block id (-1 on padding blocks)
    part_block = np.full(n_blocks_p, -1, np.int64)
    pad_win = np.zeros(n_blocks_p, np.int32)
    last_win = np.int32(0)
    for d in range(n_parts):
        s, e = bounds[d], bounds[d + 1]
        lo = d * bpp
        part_block[lo : lo + (e - s)] = np.arange(s, e)
        if e > s:
            last_win = block_win[e - 1]
        pad_win[lo : lo + bpp] = last_win

    is_real = part_block >= 0
    safe = np.where(is_real, part_block, 0)
    b_len = np.where(is_real, block_len[safe], 0)
    b_start = np.where(is_real, block_start[safe], 0)
    b_win = np.where(is_real, block_win[safe], pad_win).astype(np.int32)

    off = np.tile(np.arange(B_E, dtype=np.int64), n_blocks_p)
    blk = np.repeat(np.arange(n_blocks_p, dtype=np.int64), B_E)
    valid = off < b_len[blk]
    edge_index = np.where(
        valid,
        b_start[blk] + np.minimum(off, np.maximum(b_len[blk] - 1, 0)),
        0,
    )
    local = (dst_sorted[edge_index] - b_win[blk] * S).astype(np.int32)

    return WindowPlan(
        edge_index=edge_index,
        valid=valid.astype(np.float32),
        local=local,
        block_window=b_win,
        n_blocks=n_blocks,
        n_blocks_p=n_blocks_p,
        n_chunks=n_parts * L,
        n_windows=n_windows,
        n_rows=n_rows,
        n_parts=n_parts,
        chunks_per_part=L,
    )


def resolve_pallas_mode(requested: str = "auto") -> Optional[str]:
    """Resolve the windowed-pass Pallas dispatch once, OUTSIDE any jit.

    Returns None (XLA scan path), "tpu" (compiled Pallas kernel) or
    "interpret" (Pallas interpreter — CPU equivalence tests). "auto"
    consults the PIO_PALLAS_WINDOWED env var: "0" forces XLA,
    "interpret" forces the interpreter, "1"/unset means Pallas whenever
    the default device is a TPU. Callers embedding the result in a jit
    must treat it as a static argument (stage_windowed does)."""
    from predictionio_tpu.utils.jaxenv import on_tpu

    if requested in (None, "off"):
        return None
    if requested == "interpret":
        return "interpret"
    if requested in ("tpu", "1"):
        return "tpu" if on_tpu() else None
    env = _env_str("PIO_PALLAS_WINDOWED").strip()
    if env == "0":
        return None
    if env == "interpret":
        return "interpret"
    return "tpu" if on_tpu() else None


def windowed_gram_b(
    factors: jax.Array,  # (N_src_padded, K)
    src: jax.Array,  # (P, L, CB, B_E) int32 — rows into `factors`
    w_b: jax.Array,  # (P, L, CB, B_E) — b-vector edge weights (0 on pads)
    w_g: jax.Array,  # (P, L, CB, B_E) — gram edge weights (0 on pads)
    local: jax.Array,  # (P, L, CB, B_E) int32 — dst % S
    block_window: jax.Array,  # (n_blocks_p,) int32, part-major, sorted
    n_windows: int,
    pallas: Optional[str] = None,  # resolved mode; None = XLA scan path
    mesh=None,  # required for the sharded pallas path (P > 1)
) -> tuple[jax.Array, jax.Array]:
    """One fused edge pass → (b (N_pad, K), gram_flat (N_pad, K²)).

    b[d]    = Σ_{e→d} w_b[e] · y[src[e]]
    gram[d] = Σ_{e→d} w_g[e] · y[src[e]] ⊗ y[src[e]]   (flattened K²)

    One gather of y per edge feeds both sums. Chunk arrays are 4D
    part-major (3D (L, CB, B_E) legacy inputs are treated as P=1): the
    part axis shards over the mesh's dp axis, the scan walks each part's
    chunks in SPMD lockstep, and GSPMD turns the final block-level
    segment-sum into per-device partial sums + one ICI all-reduce per
    half-step — the TPU-native analogue of MLlib ALS's block shuffle.

    The segment reduction is either the chunked XLA one-hot matmul below
    (pallas=None) or the fused VMEM kernel in ops/windowed_pallas.py
    (pallas="tpu" / "interpret"), which skips the HBM one-hot and
    payload entirely. pallas_call has no GSPMD partitioning rule, so
    P>1 runs the kernel under shard_map over dp instead (VERDICT r4
    #2): each device runs the single-part pallas scan on its own
    contiguous block group, segment-sums its local block partials into
    the full window space, and ONE psum over dp combines them — the
    same partial-sum + all-reduce shape GSPMD derives for the XLA path.
    Requires `mesh`; without it P>1 falls back to the XLA path.
    """
    k = factors.shape[1]
    if src.ndim == 3:  # legacy single-part layout
        src, w_b, w_g, local = (
            a[None] for a in (src, w_b, w_g, local)
        )
    p = src.shape[0]
    if p > 1 and pallas is not None and mesh is not None:
        from predictionio_tpu.parallel.mesh import DATA_AXIS

        none4 = jax.sharding.PartitionSpec(None, None, None, None)
        dp4 = jax.sharding.PartitionSpec(DATA_AXIS, None, None, None)

        def local_pass(f_l, src_l, wb_l, wg_l, lc_l, bwin_l):
            # each device: the single-part pallas path over ITS blocks
            # (window ids are global, so local sums land in full rows)
            b_l, g_l = windowed_gram_b(
                f_l, src_l, wb_l, wg_l, lc_l, bwin_l, n_windows,
                pallas=pallas,
            )
            return (
                jax.lax.psum(b_l, DATA_AXIS),
                jax.lax.psum(g_l, DATA_AXIS),
            )

        from predictionio_tpu.parallel.mesh import shard_map as _shard_map

        return _shard_map(
            local_pass,
            mesh=mesh,
            in_specs=(
                jax.sharding.PartitionSpec(None, None),  # factors (gathered)
                dp4, dp4, dp4, dp4,
                jax.sharding.PartitionSpec(DATA_AXIS),
            ),
            out_specs=(
                jax.sharding.PartitionSpec(None, None),
                jax.sharding.PartitionSpec(None, None),
            ),
            # pallas_call cannot annotate varying-mesh-axes on its
            # out_shapes; replication is established manually by the
            # psums above, so disable the checker rather than the kernel
            check=False,
        )(factors, src, w_b, w_g, local, block_window)
    if p > 1:
        pallas = None  # no mesh handle → XLA path (GSPMD shards it)
    d = k + k * k
    s_rows = WINDOW_ROWS
    # scan over each part's chunks in lockstep (axis 1 → leading)
    xs = tuple(jnp.swapaxes(a, 0, 1) for a in (src, w_b, w_g, local))

    if pallas is not None:
        from predictionio_tpu.ops import windowed_pallas

        factors_t = jnp.swapaxes(factors, 0, 1)  # (K, N) — tiny

        def body(_, ch):
            s, wb, wg, lc = ch  # (1, CB, B_E)
            cb, b_e = s.shape[1], s.shape[2]
            # transposed per-chunk gather (CB, K, B_E): the edge axis
            # stays in lanes, so the pallas boundary needs no 12.8×
            # lane-pad relayout, and the gather stays chunk-sized (a
            # whole-pass gather materialized GBs and measured slower)
            y_t = (
                factors_t[:, s.reshape(-1)]
                .reshape(k, cb, b_e)
                .transpose(1, 0, 2)
            )
            pb, pg = windowed_pallas.block_partials(
                y_t,
                wb.reshape(cb, b_e),
                wg.reshape(cb, b_e),
                lc.reshape(cb, b_e),
                s_rows=s_rows,
                interpret=(pallas == "interpret"),
            )
            return None, (pb, pg)

        _, (parts_b, parts_g) = jax.lax.scan(body, None, xs)
        out_b = jax.ops.segment_sum(
            parts_b.reshape(-1, s_rows * k), block_window,
            num_segments=n_windows + 1, indices_are_sorted=True,
        )[:n_windows].reshape(n_windows * s_rows, k)
        out_g = jax.ops.segment_sum(
            parts_g.reshape(-1, s_rows * k * k), block_window,
            num_segments=n_windows + 1, indices_are_sorted=True,
        )[:n_windows].reshape(n_windows * s_rows, k * k)
        return out_b, out_g

    def body(_, ch):
        s, wb, wg, lc = ch  # (P, CB, B_E)
        y = factors[s]  # (P, CB, B_E, K)
        outer = (y[..., :, None] * y[..., None, :]).reshape(
            *y.shape[:-1], k * k
        )
        payload = jnp.concatenate(
            [y * wb[..., None], outer * wg[..., None]], axis=-1
        )  # (P, CB, B_E, D)
        onehot = (
            lc[..., None] == jnp.arange(s_rows, dtype=jnp.int32)
        ).astype(jnp.float32)  # (P, CB, B_E, S)
        part = jnp.einsum(
            "pces,pced->pcsd", onehot, payload,
            precision=jax.lax.Precision.HIGHEST,
        )  # (P, CB, S, D)
        return None, part

    _, parts = jax.lax.scan(body, None, xs)  # (L, P, CB, S, D)
    # back to part-major global block order to match block_window
    parts = jnp.swapaxes(parts, 0, 1).reshape(-1, s_rows * d)
    out = jax.ops.segment_sum(
        parts, block_window, num_segments=n_windows + 1,
        indices_are_sorted=True,
    )[:n_windows].reshape(n_windows * s_rows, d)
    return out[:, :k], out[:, k:]


def flat_gram_matvec(a_flat: jax.Array, v: jax.Array) -> jax.Array:
    """Batched (K×K)·(K,) matvec with the operator kept FLAT (N, K²).

    Reshaping to (N, K, K) would tile both trailing dims on TPU (K=10 →
    8×128 tiles, a ~20× padding blowup that made the CG matvec ~10× slower
    than its data volume warrants). Instead: elementwise-multiply by the
    tiled vector, then contract groups of K lanes with a constant (K², K)
    selection matrix on the MXU.

    out[n, i] = Σ_j a_flat[n, i·K + j] · v[n, j]
    """
    n, k2 = a_flat.shape
    k = v.shape[1]
    vt = jnp.tile(v, (1, k))  # vt[n, m] = v[n, m % K]
    sel = jnp.repeat(jnp.eye(k, dtype=a_flat.dtype), k, axis=0)  # (K², K)
    return jax.lax.dot_general(
        a_flat * vt, sel,
        dimension_numbers=(((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
    )
