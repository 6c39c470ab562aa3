"""Dense-weight-matrix ALS edge pass — MXU matmuls instead of gathers.

At MovieLens-20M density (20M ratings over 138k×26.7k ≈ 0.54% filled),
the sparse edge pass is the wrong shape for a TPU: its per-edge factor
gather runs row-serial (~2.8 ns/row measured — 49% of round-4 train
time) and its one-hot segment reduction does 28 kFLOP/edge of synthetic
MXU work anyway. Below ~1% density the TPU-native move is to stop being
sparse: store the rating matrix DENSE in bf16 (138,624×26,880×2 B =
7.4 GB — it fits a 16 GB chip) and express each ALS half-step as two
plain dense matmuls over it:

    b     =  w1(R) @ Y         w1 = 1[r>0] + α·relu(r)   (implicit)
    gram  =  wg(R) @ Z         wg = α·|r|
         (explicit:  w1 = r, wg = 1[r≠0];  Z[i] = y_i ⊗ y_i flattened)

Zeros in R contribute exactly zero to every sum, so the dense contraction
computes the same per-row normal equations the windowed edge pass builds
— with no gather, no one-hot, no edge streams, at XLA's native dense
matmul efficiency. The weight matrices w1/wg are derived from R one row
-block at a time inside a scan, so they never materialize at full size
(deriving them whole would double peak HBM and invite XLA to hoist a
7.4 GB loop-invariant).

The half-step over R's ROWS (solving users) maps blocks to outputs; the
half-step over R's COLUMNS (solving items) contracts the same row blocks
against the matching user-factor blocks and accumulates — R is stored
once, row-major, and both directions stream it exactly once per pass.

R is built on the device in the blocks it is read in (`densify`): the
training set's unique pairs arrive grouped by row block — the order the
staging gate's uniqueness sort leaves them in — and each block is built
from its own pairs and written as one contiguous slab.

Role in the reference: the MLlib-ALS hot loop
(examples/scala-parallel-recommendation/*/ALSAlgorithm.scala:50-57);
this is its below-1%-density dense reformulation, not a translation.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from predictionio_tpu.obs import devprof as _devprof

# rows of R processed per scan step; block weight derivations live in
# (ROW_BLOCK, n_cols) intermediates (~220 MB bf16 at ML-20M) instead of
# full-matrix ones. A power of two: the host's pair key splits a row by
# shift and mask (models/als.py _group_unique_pairs)
ROW_BLOCK = 2048
# lane quantum for the contraction axis
COL_PAD = 256
# slots of a block's pairs densify scatters a step; a block's last chunk
# is filled up with dead slots
_SLOT_CHUNK = 1 << 16
# densify addresses a row block's cells in int32 (the offsets it is handed
# are): the staging gate refuses a matrix this wide or wider
MAX_DENSE_COLS = (1 << 31) // ROW_BLOCK


def _dt(dense_dtype: str):
    """Compute dtype of the weight tiles / matmul operands. int8 STORAGE
    still computes in bf16 — tiles dequantize block-by-block in VMEM-
    adjacent registers."""
    return jnp.float32 if dense_dtype == "f32" else jnp.bfloat16


#: bytes per dense-R cell, by storage mode — the staging gate reads it
BYTES_PER_CELL = {"f32": 4, "bf16": 2, "int8": 1}


def storage_dtype(dense_dtype: str):
    if dense_dtype == "int8":
        return jnp.int8
    return jnp.float32 if dense_dtype == "f32" else jnp.bfloat16


# ratings tested per step of int8_scale: the chunk and its two temporaries
# stay in cache, and a scale that fails is dropped at its first bad chunk
_SCALE_CHUNK = 1 << 16


def int8_scale(vals) -> Optional[float]:
    """Smallest power-of-two (or decimal) scale making every rating an
    exact int8, or None. ML-style ratings (half-star steps ≤ 5) get
    s=2; integer counts ≤ 127 get s=1. Exactness is required — the
    dense path must train the SAME weights the sparse path would.

    One pass for the largest magnitude, then one chunked pass a
    candidate scale, with no temporary the size of the input. A power of
    two scales a float exactly in its own dtype; a decimal scale is
    tested in float64, where float32 · s is exact (24 + 7 bits)."""
    import numpy as np

    vals = np.asarray(vals).reshape(-1)
    if vals.dtype.kind != "f":
        vals = vals.astype(np.float64)
    if vals.size == 0:
        return 1.0
    m = float(max(vals.max(), -vals.min()))
    if m == 0.0:
        return 1.0
    for s in (1.0, 2.0, 4.0, 8.0, 10.0, 16.0, 20.0, 32.0, 50.0, 64.0, 100.0):
        if not m * s <= 127.0:  # the scales ascend (and a NaN): none fits
            return None
        wide = math.frexp(s)[0] != 0.5  # not a power of two
        for i in range(0, vals.size, _SCALE_CHUNK):
            chunk = vals[i : i + _SCALE_CHUNK]
            if wide:
                chunk = chunk.astype(np.float64)
            scaled = chunk if s == 1.0 else chunk * chunk.dtype.type(s)
            if not np.array_equal(scaled, np.rint(scaled)):
                break
        else:
            return s
    return None


def _precision(dense_dtype: str):
    # f32 mode exists for exactness (tests compare against the windowed
    # path); bf16 mode is the TPU throughput mode with f32 accumulation
    return (
        jax.lax.Precision.HIGHEST
        if dense_dtype == "f32"
        else jax.lax.Precision.DEFAULT
    )


def _weights(r_blk: jax.Array, implicit: bool, alpha, dt, inv_scale=None):
    """Per-block weight tiles derived in VMEM-adjacent registers — never
    materialized at matrix scale. int8-stored blocks dequantize here
    (r = q / scale), so HBM streams 1 byte per cell.

    implicit (Hu-Koren-Volinsky, signed feedback — matches
    models/als.py:_half_step_windowed):
      w1 = conf·pref = (1+α|r|)·1[r>0] = 1[r>0] + α·relu(r)
      wg = conf−1    = α·|r|
    explicit (ALS-WR):
      w1 = r, wg = 1[r≠0]  (staging rejects r==0 edges: a dense zero
      must mean "unobserved")
    """
    if r_blk.dtype == jnp.int8:
        r_blk = r_blk.astype(dt) * jnp.asarray(inv_scale, dt)
    if implicit:
        alpha = jnp.asarray(alpha, r_blk.dtype)
        w1 = (r_blk > 0).astype(r_blk.dtype) + alpha * jnp.maximum(
            r_blk, 0
        )
        wg = alpha * jnp.abs(r_blk)
    else:
        w1 = r_blk
        wg = (r_blk != 0).astype(r_blk.dtype)
    return w1.astype(dt), wg.astype(dt)


def _yz(fixed: jax.Array, dt):
    """Cast factor operands: Y (N, K) and flattened outer products
    Z (N, K²) — the K²-lane payload the gram matmul contracts."""
    n, k = fixed.shape
    y = fixed.astype(dt)
    z = (fixed[:, :, None] * fixed[:, None, :]).reshape(n, k * k).astype(dt)
    return y, z


@partial(
    jax.jit,
    static_argnames=("implicit", "dense_dtype", "row_block", "scale"),
)
def dense_row_pass(
    r: jax.Array,  # (n_rows_p, n_cols_p) storage-dtype rating matrix
    fixed: jax.Array,  # (n_cols_p, K) f32 — the fixed side's factors
    *,
    implicit: bool,
    alpha: float,
    dense_dtype: str = "bf16",
    row_block: int = ROW_BLOCK,
    scale: float = 1.0,
) -> tuple[jax.Array, jax.Array]:
    """(b (n_rows_p, K), gram_corr_flat (n_rows_p, K²)) for R's rows."""
    n_rows, n_cols = r.shape
    k = fixed.shape[1]
    dt = _dt(dense_dtype)
    prec = _precision(dense_dtype)
    y, z = _yz(fixed, dt)

    # Two dots, NOT one stacked dot: concatenating [w1; wg] into a
    # single (2·BR, n_cols) operand would stream R once instead of
    # twice, but XLA materializes the concatenated bf16 operand in HBM
    # (~write+read of 2× the R footprint per pass) — A/B-measured 2.5×
    # SLOWER at ML-20M (1.50 s vs 0.59 s per train). The two-dot form
    # fuses each weight derivation straight into its dot's operand
    # read, so the only HBM cost is reading int8 R twice.
    def blk(_, r_blk):  # (row_block, n_cols)
        w1, wg = _weights(r_blk, implicit, alpha, dt, 1.0 / scale)
        b = jax.lax.dot_general(
            w1, y, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec,
        )
        corr = jax.lax.dot_general(
            wg, z, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec,
        )
        return None, (b, corr)

    _, (b, corr) = jax.lax.scan(
        blk, None, r.reshape(n_rows // row_block, row_block, n_cols)
    )
    return b.reshape(n_rows, k), corr.reshape(n_rows, k * k)


# device profiling (ISSUE 3): top-level dispatches of these kernels (the
# alternating train loop traces THROUGH the wrappers — nested calls pass
# straight to the jit) land in the executable registry
dense_row_pass = _devprof.instrument("ops.dense_row_pass", dense_row_pass)


@partial(
    jax.jit,
    static_argnames=("implicit", "dense_dtype", "row_block", "scale"),
)
def dense_col_pass(
    r: jax.Array,  # (n_rows_p, n_cols_p) — SAME row-major storage
    fixed: jax.Array,  # (n_rows_p, K) f32 — factors of R's row side
    *,
    implicit: bool,
    alpha: float,
    dense_dtype: str = "bf16",
    row_block: int = ROW_BLOCK,
    scale: float = 1.0,
) -> tuple[jax.Array, jax.Array]:
    """(b (n_cols_p, K), gram_corr_flat (n_cols_p, K²)) for R's columns.

    Contracts the same row blocks the row pass streams (an Aᵀ·B GEMM per
    block — the MXU consumes either operand orientation natively, no
    materialized transpose of R)."""
    n_rows, n_cols = r.shape
    k = fixed.shape[1]
    dt = _dt(dense_dtype)
    prec = _precision(dense_dtype)
    y, z = _yz(fixed, dt)
    nb = n_rows // row_block
    xs = (
        r.reshape(nb, row_block, n_cols),
        y.reshape(nb, row_block, k),
        z.reshape(nb, row_block, k * k),
    )

    def blk(acc, ch):
        r_blk, y_blk, z_blk = ch
        w1, wg = _weights(r_blk, implicit, alpha, dt, 1.0 / scale)
        b_acc, c_acc = acc
        # two dots (see dense_row_pass: the stacked-operand fusion was
        # measured 2.5× slower — XLA materializes the concat)
        b_acc = b_acc + jax.lax.dot_general(
            w1, y_blk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec,
        )
        c_acc = c_acc + jax.lax.dot_general(
            wg, z_blk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec,
        )
        return (b_acc, c_acc), None

    acc0 = (
        jnp.zeros((n_cols, k), jnp.float32),
        jnp.zeros((n_cols, k * k), jnp.float32),
    )
    (b, corr), _ = jax.lax.scan(blk, acc0, xs)
    return b, corr


dense_col_pass = _devprof.instrument("ops.dense_col_pass", dense_col_pass)


def dead_slots(starts) -> int:
    """Slots densify runs beyond the pairs, for these block starts: every
    block walks its pairs in whole chunks. What raggedness costs."""
    import numpy as np

    per_block = np.diff(np.asarray(starts, np.int64))
    return int((-per_block % _SLOT_CHUNK).sum())


@partial(jax.jit, static_argnames=("n_rows_p", "n_cols_p"))
def densify(
    offsets: jax.Array,  # (E,) int32 — cell inside the pair's row block
    values: jax.Array,  # (E,) storage dtype — what that cell holds
    starts: jax.Array,  # (n_blocks + 1,) int32 — block b is [b], [b + 1]
    *,
    n_rows_p: int,
    n_cols_p: int,
) -> jax.Array:
    """Build the dense padded rating matrix from its unique pairs — ONCE
    per training set, on device (the matrix never crosses the host
    link). The pairs come grouped by ROW_BLOCK of rows and ascending by
    cell inside a block (models/als.py `_group_unique_pairs`: the order
    the staging gate's uniqueness sort leaves them in), the values
    already as stored (int8 codes round(r·scale), exactness gated by
    int8_scale at staging).

    A scan over the row blocks: a step zeroes one flat block of
    ROW_BLOCK · n_cols_p cells, scatters its own pairs into it — 1-D,
    _SLOT_CHUNK slots a scatter, as many chunks as the block has pairs
    for — and emits it as one contiguous slab; the stacked slabs ARE the
    matrix. A chunk's slots past the block's end aim past the block (so
    a chunk's indices still ascend, and XLA is told: it sorts them
    otherwise) and are dropped: a ragged block costs dead slots
    (`dead_slots`), and a block that holds most of the pairs takes more
    chunks, nothing else.
    The temporaries are one block and one chunk whatever the pair count:
    a 2-D scatter's (E, 2) index operand, lane-padded to (E, 128) int32,
    was 10.2 GB at ML-20M (PR 21)."""
    cells = ROW_BLOCK * n_cols_p
    # a block's last chunk may reach past the last pair
    offsets = jnp.pad(offsets, (0, _SLOT_CHUNK))
    values = jnp.pad(values, (0, _SLOT_CHUNK))
    lane = jnp.arange(_SLOT_CHUNK, dtype=jnp.int32)

    def block(_, b):
        start, end = starts[b], starts[b + 1]

        def chunk(i, blk):
            at = start + i * _SLOT_CHUNK
            off = jax.lax.dynamic_slice_in_dim(offsets, at, _SLOT_CHUNK)
            val = jax.lax.dynamic_slice_in_dim(values, at, _SLOT_CHUNK)
            off = jnp.where(at + lane < end, off, cells)
            return blk.at[off].set(
                val, mode="drop", indices_are_sorted=True
            )

        blk = jax.lax.fori_loop(
            0, -(-(end - start) // _SLOT_CHUNK), chunk,
            jnp.zeros(cells, values.dtype),
        )
        return None, blk.reshape(ROW_BLOCK, n_cols_p)

    _, r = jax.lax.scan(
        block, None, jnp.arange(n_rows_p // ROW_BLOCK, dtype=jnp.int32)
    )
    return r.reshape(n_rows_p, n_cols_p)


densify = _devprof.instrument("ops.densify", densify)
