"""Alternating least squares (implicit + explicit) as an XLA program.

Replaces the reference templates' delegation to Spark MLlib ALS
(`ALS.trainImplicit` / `ALS.train`, used by
examples/scala-parallel-recommendation/*/ALSAlgorithm.scala:50-57 and the
similarproduct / ecommerce templates).

TPU-first design (NOT a port of MLlib's block-partitioned shuffle ALS):
- Interactions are a COO edge list staged to device once; each ALS
  half-step solves every row's k×k normal-equation system *simultaneously*
  with batched conjugate gradient, where the Gram-correction matvec is a
  matrix-free edge gather + segment-sum (ops/segment.py:edge_matvec).
  Memory stays O(E·k + (U+I)·k); no per-user k×k materialization, no
  factor-block shuffle.
- The whole alternating loop runs inside one jit with static shapes and
  `lax.fori_loop`; edges are pre-sorted per side on the host so segment
  reductions take the sorted fast path.
- Multi-chip: edges are sharded over the mesh's data axis; factor matrices
  are row-sharded over the model axis (replicated when mp == 1). GSPMD
  turns the segment-sum scatters into local partial sums + ICI
  all-reduce/all-gather — the TPU-native analogue of MLlib's shuffle
  (see parallel/mesh.py for mesh construction).

Implicit objective (Hu-Koren-Volinsky): confidence c = 1 + alpha·r,
preference p = 1; per-user system (YᵀY + Yᵀ(Cᵤ−I)Y + λI) xᵤ = YᵀCᵤpᵤ.
Explicit (ALS-WR): Σ_obs (r − x·y)² + λ(nᵤ‖xᵤ‖² + nᵢ‖yᵢ‖²).
"""

from __future__ import annotations

import io
import json
import logging
import os
from dataclasses import dataclass, field
from functools import partial
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from predictionio_tpu.data.store.bimap import BiMap
from predictionio_tpu.obs import devprof as _devprof
from predictionio_tpu.obs import spans as _spans
from predictionio_tpu.obs.jaxmon import compile_snapshot
from predictionio_tpu.utils.env import env_int, env_str
from predictionio_tpu.ops.segment import (
    batched_cg,
    chunked_edge_matvec,
    chunked_gram_edge_sum,
    chunked_weighted_edge_sum,
    f32_gram,
)
from predictionio_tpu.ops.windowed import (
    flat_gram_matvec,
    plan_windows,
    windowed_gram_b,
)

# ranks up to this solve via explicitly-built per-row K×K operators (one
# edge pass per half-step); beyond it the matrix-free CG path keeps memory
# O(E·K) — the (N, K, K) operator tensor would start to dominate HBM
GRAM_SOLVER_MAX_RANK = 32
from predictionio_tpu.ops.topk import NEG_INF, masked_top_k


@dataclass(frozen=True)
class ALSParams:
    rank: int = 10
    iterations: int = 20
    lambda_: float = 0.01
    alpha: float = 1.0  # implicit confidence scale
    implicit_prefs: bool = True
    cg_iterations: int = 3
    seed: int = 3
    # max edges per device program step; larger edge lists are scanned in
    # chunks so the lane-padded (E, K) gather intermediates stay bounded
    # (at ML-20M scale a single-shot build OOMs a 16G chip)
    edge_chunk_size: int = 1 << 21


@dataclass
class ALSFactors:
    """Trained factor matrices + id vocabularies."""

    user_factors: np.ndarray  # (U, K) float32
    item_factors: np.ndarray  # (I, K) float32
    user_vocab: BiMap  # user id → row
    item_vocab: BiMap  # item id → row
    params: ALSParams = field(default_factory=ALSParams)

    # -- persistence (replaces template IPersistentModel save/load) --------
    def to_bytes(self) -> bytes:
        buf = io.BytesIO()
        np.savez_compressed(
            buf,
            user_factors=self.user_factors,
            item_factors=self.item_factors,
            user_ids=np.array(list(self.user_vocab.to_dict().keys()), dtype=object),
            user_idx=np.array(list(self.user_vocab.to_dict().values()), dtype=np.int64),
            item_ids=np.array(list(self.item_vocab.to_dict().keys()), dtype=object),
            item_idx=np.array(list(self.item_vocab.to_dict().values()), dtype=np.int64),
            params=np.frombuffer(
                json.dumps(self.params.__dict__).encode(), dtype=np.uint8
            ),
        )
        return buf.getvalue()

    @staticmethod
    def from_bytes(data: bytes) -> "ALSFactors":
        with np.load(io.BytesIO(data), allow_pickle=True) as z:
            params = ALSParams(
                **json.loads(bytes(z["params"].tobytes()).decode())
            )
            user_vocab = BiMap(
                dict(zip(z["user_ids"].tolist(), z["user_idx"].tolist()))
            )
            item_vocab = BiMap(
                dict(zip(z["item_ids"].tolist(), z["item_idx"].tolist()))
            )
            return ALSFactors(
                user_factors=z["user_factors"],
                item_factors=z["item_factors"],
                user_vocab=user_vocab,
                item_vocab=item_vocab,
                params=params,
            )


# ---------------------------------------------------------------------------
# Core solver — windowed (scatter-free) path
# ---------------------------------------------------------------------------


def _half_step_windowed(
    fixed: jax.Array,  # (N_fixed_padded, K) — pad rows are exactly zero
    src: jax.Array,  # (n_chunks, CB, B_E) — rows into `fixed`
    val: jax.Array,  # (n_chunks, CB, B_E) — ratings (0 on pads)
    ok: jax.Array,  # (n_chunks, CB, B_E) — 1.0 real edge / 0.0 padding
    loc: jax.Array,  # (n_chunks, CB, B_E) — dst % WINDOW_ROWS
    bwin: jax.Array,  # (n_blocks_p,) — output window per block
    degree: jax.Array,  # (N_dst_padded,) — for ALS-WR reg (explicit only)
    x0: jax.Array,  # (N_dst_padded, K) warm start
    *,
    n_windows: int,
    implicit: bool,
    lam: float,
    alpha: float,
    cg_iterations: int,
    pallas_mode: Optional[str] = None,
    mesh=None,
) -> jax.Array:
    """One ALS half-step with the windowed one-hot reduction: a single
    fused edge pass builds b and all per-row gram corrections, then CG
    runs dense on the FLAT (N, K²) operators (flat_gram_matvec)."""
    n_dst, k = x0.shape
    if implicit:
        # implicit operator: YᵀY + Σ(c−1)yyᵀ + λI  (global gram term)
        gram = f32_gram(fixed)
        conf = 1.0 + alpha * jnp.abs(val)
        pref = (val > 0).astype(jnp.float32)
        w_b = conf * pref * ok
        w_g = (conf - 1.0) * ok
        b, corr_flat = windowed_gram_b(
            fixed, src, w_b, w_g, loc, bwin, n_windows,
            pallas=pallas_mode, mesh=mesh,
        )
        base = gram + lam * jnp.eye(k, dtype=jnp.float32)
        a_flat = corr_flat + base.reshape(1, k * k)
    else:
        # explicit (ALS-WR) operator: Σ_obs yyᵀ + λ·max(deg,1)·I
        w_b = val * ok
        w_g = ok
        b, corr_flat = windowed_gram_b(
            fixed, src, w_b, w_g, loc, bwin, n_windows,
            pallas=pallas_mode, mesh=mesh,
        )
        reg = lam * jnp.maximum(degree, 1.0)
        eye_flat = jnp.eye(k, dtype=jnp.float32).reshape(1, k * k)
        a_flat = corr_flat + reg[:, None] * eye_flat

    def matvec(v):
        return flat_gram_matvec(a_flat, v)

    return batched_cg(matvec, b, x0, cg_iterations)


# ---------------------------------------------------------------------------
# Core solver — dense-W fast path (sub-1%-density rating matrices)
# ---------------------------------------------------------------------------

# auto-dispatch bound for the dense rating matrix: PIO_DENSE_ALS_BYTES,
# whose registered default (utils/env.py) is 9e9 (ML-20M needs 7.45 GB of
# a 16 GB chip in bf16); PIO_DENSE_ALS=0 disables, =1 forces where it fits
# below this edge count the windowed path's staging is already cheap and
# CPU test suites compare against f32-exact references — auto keeps them
# on the windowed path unless PIO_DENSE_ALS=1 opts in
DENSE_AUTO_MIN_EDGES = 1_000_000


def _dense_half_step(
    r: jax.Array,
    fixed: jax.Array,  # factors of the side NOT being solved
    degree: jax.Array,  # (n_solved_p,) — -1 marks padding rows
    x0: jax.Array,
    *,
    solve_rows: bool,  # True: solve R's row side; False: its column side
    implicit: bool,
    lam: float,
    alpha: float,
    cg_iterations: int,
    dense_dtype: str,
    scale: float = 1.0,
    pallas_mode=None,
) -> jax.Array:
    """One ALS half-step with b/gram built by dense matmuls over R.

    Identical operator assembly + CG to the windowed path — only the
    edge pass differs: the fused Pallas kernel (ops/dense_pallas.py —
    ONE R read per pass, both weight tiles derived in VMEM) when
    `pallas_mode` is set and the storage is int8 with clean tile
    divisors, else the XLA two-dot scan (ops/dense.py). Padding rows
    have all-zero R and b=0, x0=0, so CG freezes them at zero exactly
    like window padding."""
    from predictionio_tpu.ops import dense

    k = x0.shape[1]
    use_kernel = pallas_mode is not None and r.dtype == jnp.int8
    if use_kernel:
        from predictionio_tpu.ops import dense_pallas

        rt, ct = dense_pallas.pick_tiles(*r.shape)
        use_kernel = rt > 0 and ct > 0
    if use_kernel:
        y32 = fixed.astype(jnp.float32)
        z32 = (
            fixed[:, :, None] * fixed[:, None, :]
        ).reshape(fixed.shape[0], k * k).astype(jnp.float32)
        ascale = jnp.asarray(
            [alpha / scale if implicit else 1.0 / scale], jnp.float32
        )
        fused = (
            dense_pallas.fused_row_pass
            if solve_rows
            else dense_pallas.fused_col_pass
        )
        b, corr_flat = fused(
            r, y32, z32, ascale, implicit=implicit,
            interpret=(pallas_mode == "interpret"),
            row_tile=rt, col_tile=ct,
        )
    else:
        edge_pass = (
            dense.dense_row_pass if solve_rows else dense.dense_col_pass
        )
        b, corr_flat = edge_pass(
            r, fixed, implicit=implicit, alpha=alpha,
            dense_dtype=dense_dtype, scale=scale,
        )
    if implicit:
        gram = f32_gram(fixed)
        base = gram + lam * jnp.eye(k, dtype=jnp.float32)
        a_flat = corr_flat + base.reshape(1, k * k)
    else:
        reg = lam * jnp.maximum(degree, 1.0)
        eye_flat = jnp.eye(k, dtype=jnp.float32).reshape(1, k * k)
        a_flat = corr_flat + reg[:, None] * eye_flat

    def matvec(v):
        return flat_gram_matvec(a_flat, v)

    return batched_cg(matvec, b, x0, cg_iterations)


@partial(
    jax.jit,
    static_argnames=(
        "rank", "iterations", "implicit", "cg_iterations", "dense_dtype",
        "scale", "pallas_mode",
    ),
)
def _train_jit_dense(
    r: jax.Array,  # (n_users_p, n_items_p) dense storage-dtype ratings
    user_deg: jax.Array,  # (n_users_p,), -1 on padding rows
    item_deg: jax.Array,  # (n_items_p,)
    uf0=None,
    itf0=None,
    *,
    rank: int,
    iterations: int,
    implicit: bool,
    lam: float,
    alpha: float,
    cg_iterations: int,
    seed: int,
    dense_dtype: str = "bf16",
    scale: float = 1.0,
    pallas_mode=None,
):
    """Whole alternating loop on the dense-W path: every half-step is two
    dense matmuls + the shared flat-operator CG. R enters as a jit
    ARGUMENT (a loop invariant produced by fused ops would risk the TPU
    fori-loop miscompile batched_cg's docstring records)."""
    n_users_p, n_items_p = r.shape
    if uf0 is not None and itf0 is not None:
        uf, itf = uf0, itf0
    else:
        ku, ki = jax.random.split(jax.random.PRNGKey(seed))
        # partitionable threefry: element i's bits depend only on (key,
        # i), not the array size — so the sharded trains (whose padded
        # shapes differ with dp/mp) slice IDENTICAL inits from their
        # larger draws and match this path exactly (newer jax defaults
        # to this; the pin makes the parity hold on every version)
        with jax.threefry_partitionable(True):
            uf = (
                jax.random.normal(ku, (n_users_p, rank), jnp.float32)
                / jnp.sqrt(rank)
            ) * (user_deg >= 0)[:, None]
            itf = (
                jax.random.normal(ki, (n_items_p, rank), jnp.float32)
                / jnp.sqrt(rank)
            ) * (item_deg >= 0)[:, None]

    def body(_, fs):
        uf, itf = fs
        uf = _dense_half_step(
            r, itf, user_deg, uf, solve_rows=True, implicit=implicit,
            lam=lam, alpha=alpha, cg_iterations=cg_iterations,
            dense_dtype=dense_dtype, scale=scale,
            pallas_mode=pallas_mode,
        )
        itf = _dense_half_step(
            r, uf, item_deg, itf, solve_rows=False, implicit=implicit,
            lam=lam, alpha=alpha, cg_iterations=cg_iterations,
            dense_dtype=dense_dtype, scale=scale,
            pallas_mode=pallas_mode,
        )
        return uf, itf

    return jax.lax.fori_loop(0, iterations, body, (uf, itf))


# device profiling (ISSUE 3): each top-level train program is a named
# executable in the registry. scale_by="iterations" corrects XLA's HLO
# cost analysis counting the fori_loop body once regardless of trip
# count (see obs/devprof.py); memory_analysis stays off — these are the
# multi-second compiles a duplicate AOT compile must not double.
_train_jit_dense = _devprof.instrument(
    "als.train_dense", _train_jit_dense, scale_by="iterations"
)


@partial(
    jax.jit,
    static_argnames=(
        "rank", "iterations", "implicit", "cg_iterations", "dense_dtype",
        "scale",
    ),
)
def _train_jit_dense_grid(
    r: jax.Array,
    user_deg: jax.Array,
    item_deg: jax.Array,
    lams: jax.Array,  # (G,)
    alphas: jax.Array,  # (G,)
    *,
    rank: int,
    iterations: int,
    implicit: bool,
    cg_iterations: int,
    seed: int,
    dense_dtype: str = "bf16",
    scale: float = 1.0,
):
    """(λ, α) grid on the dense path: R is closed over (vmap broadcasts
    it — ONE device matrix serves every grid point); the weight
    derivations and solves batch over the grid axis."""

    def one(lam, alpha):
        return _train_jit_dense(
            r, user_deg, item_deg,
            rank=rank, iterations=iterations, implicit=implicit,
            lam=lam, alpha=alpha, cg_iterations=cg_iterations, seed=seed,
            dense_dtype=dense_dtype, scale=scale,
        )

    return jax.vmap(one)(lams, alphas)


@partial(
    jax.jit,
    static_argnames=(
        "rank", "iterations", "implicit", "cg_iterations", "dense_dtype",
        "scale", "mesh",
    ),
)
def _train_jit_dense_sharded(
    r: jax.Array,  # (n_users_p, n_items_p) — row-sharded over dp
    user_deg: jax.Array,  # (n_users_p,) — row-sharded over dp
    item_deg: jax.Array,  # (n_items_p,) — replicated
    uf0=None,  # (n_users_p, rank) row-sharded / None
    itf0=None,  # (n_items_p, rank) replicated / None
    *,
    rank: int,
    iterations: int,
    implicit: bool,
    lam: float,
    alpha: float,
    cg_iterations: int,
    seed: int,
    dense_dtype: str = "bf16",
    scale: float = 1.0,
    mesh=None,
):
    """Dense-W alternating loop shard_map'd over the mesh.

    With mp == 1 (the PR-7 shape): the rating matrix is ROW-sharded
    over dp (each device owns a slab of users); factors stay
    replicated. Per iteration:

      user half: each device solves ITS user rows from its local slab —
                 fully local, zero collectives;
      item half: each device contracts its slab against its local user
                 factors into partial (n_items, ·) sums; ONE psum over
                 dp combines them and every device solves the (small)
                 item systems redundantly.

    With mp > 1 (ISSUE 10 model-axis sharding, activated by the
    engine.json `mesh` key): R is 2-D block-sharded (users over dp,
    items over mp), USER factors are row-sharded over dp and ITEM
    factors row-sharded over mp — no device ever holds a full factor
    matrix, so the factor state scales past one chip's HBM. Each
    half-step's cross-side normal-equation assembly becomes partial
    per-block sums + ONE all-reduce over the OPPOSITE axis (user half:
    psum over mp assembles b/Gram from the item shards; item half: psum
    over dp), then each shard solves only the systems of the rows it
    owns — the gather/all-reduce shape of MLlib ALS's block shuffle.

    This is the TPU-native shape of MLlib ALS's block distribution: the
    ratings never move, only the (tiny) factor matrices ride ICI.

    VALIDATION CAVEAT: the alternating fori_loop here reads the large
    sharded slab inside shard_map — the shape of program the recorded
    TPU fori-loop miscompile (batched_cg's docstring) bit at FULL scale
    while small shapes passed. This rig has one chip, so the sharded
    path is validated on CPU meshes + the dryrun only; the first real
    multi-chip deployment must check full-scale finiteness and
    agreement with the windowed path before trusting factors."""
    from predictionio_tpu.ops import dense as dense_ops
    from predictionio_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS

    n_users_p, n_items_p = r.shape
    if int(mesh.shape.get(MODEL_AXIS, 1)) > 1:
        return _dense_sharded_2d(
            r, user_deg, item_deg, uf0, itf0,
            rank=rank, iterations=iterations, implicit=implicit,
            lam=lam, alpha=alpha, cg_iterations=cg_iterations,
            seed=seed, dense_dtype=dense_dtype, scale=scale, mesh=mesh,
        )
    spec_r = jax.sharding.PartitionSpec(DATA_AXIS, None)
    spec_v = jax.sharding.PartitionSpec(DATA_AXIS)
    rep2 = jax.sharding.PartitionSpec(None, None)
    rep1 = jax.sharding.PartitionSpec(None)

    def local_train(r_l, udeg_l, ideg, uf0_l, itf0_r):
        n_u_local = r_l.shape[0]
        d = jax.lax.axis_index(DATA_AXIS)
        if uf0_l is not None and itf0_r is not None:
            uf_l, itf = uf0_l, itf0_r
        else:
            ku, ki = jax.random.split(jax.random.PRNGKey(seed))
            # generate the FULL init on every device (replicated
            # compute, deterministic) and slice the local slab so the
            # sharded run matches the single-device run exactly;
            # partitionable threefry makes the draw shape-stable, so
            # the differently-padded single-device init is a prefix
            with jax.threefry_partitionable(True):
                uf_full = (
                    jax.random.normal(ku, (n_users_p, rank), jnp.float32)
                    / jnp.sqrt(rank)
                )
                itf = (
                    jax.random.normal(ki, (n_items_p, rank), jnp.float32)
                    / jnp.sqrt(rank)
                ) * (ideg >= 0)[:, None]
            uf_l = jax.lax.dynamic_slice_in_dim(
                uf_full, d * n_u_local, n_u_local
            ) * (udeg_l >= 0)[:, None]

        k = rank
        eye_flat = jnp.eye(k, dtype=jnp.float32).reshape(1, k * k)

        def body(_, fs):
            uf_l, itf = fs
            # user half: local rows, local slab — no collectives
            uf_l = _dense_half_step(
                r_l, itf, udeg_l, uf_l, solve_rows=True,
                implicit=implicit, lam=lam, alpha=alpha,
                cg_iterations=cg_iterations, dense_dtype=dense_dtype,
                scale=scale,
            )
            # item half: partial sums from the local slab + ONE psum
            b, corr_flat = dense_ops.dense_col_pass(
                r_l, uf_l, implicit=implicit, alpha=alpha,
                dense_dtype=dense_dtype, scale=scale,
            )
            b = jax.lax.psum(b, DATA_AXIS)
            corr_flat = jax.lax.psum(corr_flat, DATA_AXIS)
            if implicit:
                gram = jax.lax.psum(f32_gram(uf_l), DATA_AXIS)
                base = gram + lam * jnp.eye(k, dtype=jnp.float32)
                a_flat = corr_flat + base.reshape(1, k * k)
            else:
                reg = lam * jnp.maximum(ideg, 1.0)
                a_flat = corr_flat + reg[:, None] * eye_flat

            def matvec(v):
                return flat_gram_matvec(a_flat, v)

            itf = batched_cg(matvec, b, itf, cg_iterations)
            return uf_l, itf

        return jax.lax.fori_loop(0, iterations, body, (uf_l, itf))

    # shard_map cannot spec None leaves — close over absent inits
    if uf0 is None or itf0 is None:
        fn = lambda r_l, udeg_l, ideg: local_train(
            r_l, udeg_l, ideg, None, None
        )
        args = (r, user_deg, item_deg)
        in_specs = (spec_r, spec_v, rep1)
    else:
        fn = local_train
        args = (r, user_deg, item_deg, uf0, itf0)
        in_specs = (spec_r, spec_v, rep1, spec_r, rep2)
    from predictionio_tpu.parallel.mesh import shard_map as _shard_map

    return _shard_map(
        fn,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=(spec_r, rep2),
        check=False,
    )(*args)


def _dense_sharded_2d(
    r: jax.Array,  # (n_users_p, n_items_p) — block-sharded (dp, mp)
    user_deg: jax.Array,  # (n_users_p,) — sharded over dp
    item_deg: jax.Array,  # (n_items_p,) — sharded over mp
    uf0,  # (n_users_p, rank) sharded over dp / None
    itf0,  # (n_items_p, rank) sharded over mp / None
    *,
    rank: int,
    iterations: int,
    implicit: bool,
    lam: float,
    alpha: float,
    cg_iterations: int,
    seed: int,
    dense_dtype: str,
    scale: float,
    mesh,
):
    """The mp > 1 body of `_train_jit_dense_sharded` (ISSUE 10): R is
    2-D block-sharded, user factors live row-sharded over dp and item
    factors row-sharded over mp. Each half-step runs the SAME
    dense_row/col_pass kernels on the local block; the cross-side
    normal-equation assembly is one psum over the opposite axis (plus
    one for the implicit-mode global Gram), then each shard solves only
    its own rows' K×K systems. Inits are generated replicated from the
    same PRNG stream as the single-device path and sliced, so mp-
    sharded factors match the unsharded solve to f32 reduction-order
    tolerance."""
    from predictionio_tpu.ops import dense as dense_ops
    from predictionio_tpu.parallel.mesh import (
        DATA_AXIS,
        MODEL_AXIS,
        shard_map as _shard_map,
    )

    n_users_p, n_items_p = r.shape
    spec_r = jax.sharding.PartitionSpec(DATA_AXIS, MODEL_AXIS)
    spec_u1 = jax.sharding.PartitionSpec(DATA_AXIS)
    spec_i1 = jax.sharding.PartitionSpec(MODEL_AXIS)
    spec_u2 = jax.sharding.PartitionSpec(DATA_AXIS, None)
    spec_i2 = jax.sharding.PartitionSpec(MODEL_AXIS, None)

    def local_train(r_l, udeg_l, ideg_l, uf0_l, itf0_l):
        n_u_local, n_i_local = r_l.shape
        d = jax.lax.axis_index(DATA_AXIS)
        m = jax.lax.axis_index(MODEL_AXIS)
        if uf0_l is not None and itf0_l is not None:
            uf_l, itf_l = uf0_l, itf0_l
        else:
            ku, ki = jax.random.split(jax.random.PRNGKey(seed))
            # full init generated on every device (replicated compute,
            # deterministic), sliced to the local slab — identical
            # numbers to the single-device init (partitionable threefry
            # makes the draw a shape-stable prefix, see _train_jit_dense)
            with jax.threefry_partitionable(True):
                uf_full = (
                    jax.random.normal(ku, (n_users_p, rank), jnp.float32)
                    / jnp.sqrt(rank)
                )
                itf_full = (
                    jax.random.normal(ki, (n_items_p, rank), jnp.float32)
                    / jnp.sqrt(rank)
                )
            uf_l = jax.lax.dynamic_slice_in_dim(
                uf_full, d * n_u_local, n_u_local
            ) * (udeg_l >= 0)[:, None]
            itf_l = jax.lax.dynamic_slice_in_dim(
                itf_full, m * n_i_local, n_i_local
            ) * (ideg_l >= 0)[:, None]

        k = rank
        eye = jnp.eye(k, dtype=jnp.float32)
        eye_flat = eye.reshape(1, k * k)

        def body(_, fs):
            uf_l, itf_l = fs
            # user half: partial sums over MY item columns; psum over
            # mp assembles each user row's full b and Gram correction
            b, corr_flat = dense_ops.dense_row_pass(
                r_l, itf_l, implicit=implicit, alpha=alpha,
                dense_dtype=dense_dtype, scale=scale,
            )
            b = jax.lax.psum(b, MODEL_AXIS)
            corr_flat = jax.lax.psum(corr_flat, MODEL_AXIS)
            if implicit:
                gram = jax.lax.psum(f32_gram(itf_l), MODEL_AXIS)
                a_flat = corr_flat + (gram + lam * eye).reshape(1, k * k)
            else:
                reg = lam * jnp.maximum(udeg_l, 1.0)
                a_flat = corr_flat + reg[:, None] * eye_flat
            uf_l = batched_cg(
                lambda v: flat_gram_matvec(a_flat, v), b, uf_l,
                cg_iterations,
            )
            # item half: partial sums over MY user rows; psum over dp
            b, corr_flat = dense_ops.dense_col_pass(
                r_l, uf_l, implicit=implicit, alpha=alpha,
                dense_dtype=dense_dtype, scale=scale,
            )
            b = jax.lax.psum(b, DATA_AXIS)
            corr_flat = jax.lax.psum(corr_flat, DATA_AXIS)
            if implicit:
                gram = jax.lax.psum(f32_gram(uf_l), DATA_AXIS)
                a_flat = corr_flat + (gram + lam * eye).reshape(1, k * k)
            else:
                reg = lam * jnp.maximum(ideg_l, 1.0)
                a_flat = corr_flat + reg[:, None] * eye_flat
            itf_l = batched_cg(
                lambda v: flat_gram_matvec(a_flat, v), b, itf_l,
                cg_iterations,
            )
            return uf_l, itf_l

        return jax.lax.fori_loop(0, iterations, body, (uf_l, itf_l))

    # shard_map cannot spec None leaves — close over absent inits
    if uf0 is None or itf0 is None:
        fn = lambda r_l, udeg_l, ideg_l: local_train(
            r_l, udeg_l, ideg_l, None, None
        )
        args = (r, user_deg, item_deg)
        in_specs = (spec_r, spec_u1, spec_i1)
    else:
        fn = local_train
        args = (r, user_deg, item_deg, uf0, itf0)
        in_specs = (spec_r, spec_u1, spec_i1, spec_u2, spec_i2)
    return _shard_map(
        fn,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=(spec_u2, spec_i2),
        check=False,
    )(*args)


_train_jit_dense_grid = _devprof.instrument(
    "als.train_dense_grid", _train_jit_dense_grid, scale_by="iterations"
)
_train_jit_dense_sharded = _devprof.instrument(
    "als.train_dense_sharded", _train_jit_dense_sharded,
    scale_by="iterations",
)


def _run_program(program, *args, **kwargs):
    """Run a whole-train program under the `als.train.program` span:
    from dispatch until the factors are ready on the device."""
    compiles = compile_snapshot()[0]
    with _spans.span(
        "als.train.program", iterations=kwargs["iterations"]
    ) as sp:
        out = jax.block_until_ready(program(*args, **kwargs))
        sp.attrs["jit_compiles"] = compile_snapshot()[0] - compiles
    return out


def _copy_back(uf, itf, n_users: int, n_items: int):
    """Both factor tables, device -> host, cut to the real rows."""
    with _spans.span("als.train.copy_back"):
        return np.asarray(uf)[:n_users], np.asarray(itf)[:n_items]


@dataclass
class StagedDenseTrain:
    """A dense-path train with the rating matrix resident on device.

    Mirrors StagedWindowedTrain: built once per training set by
    `stage_dense`; `run()` re-executes the compiled alternating loop
    with zero further host→device traffic."""

    device_args: tuple
    static_kwargs: dict
    n_users: int
    n_items: int

    def run(self) -> tuple[jax.Array, jax.Array]:
        if self.static_kwargs.get("mesh") is not None:
            kwargs = {
                k: v
                for k, v in self.static_kwargs.items()
                if k != "pallas_mode"
            }
            return _run_program(
                _train_jit_dense_sharded, *self.device_args, **kwargs
            )
        kwargs = {
            k: v for k, v in self.static_kwargs.items() if k != "mesh"
        }
        return _run_program(_train_jit_dense, *self.device_args, **kwargs)

    def factors(self, uf, itf) -> tuple[np.ndarray, np.ndarray]:
        return _copy_back(uf, itf, self.n_users, self.n_items)


def _padded_dims(n_users: int, n_items: int, dp: int = 1, mp: int = 1):
    """(rows, columns) of the dense matrix: user rows pad to whole
    ROW_BLOCKs of every dp device's slab, item columns to whole COL_PAD
    column blocks of every mp device (ISSUE 10)."""
    from predictionio_tpu.ops.dense import COL_PAD, ROW_BLOCK

    return (
        -(-n_users // (ROW_BLOCK * dp)) * (ROW_BLOCK * dp),
        -(-n_items // (COL_PAD * mp)) * (COL_PAD * mp),
    )


def dense_matrix_bytes(
    n_users: int, n_items: int, dense_dtype: str = "bf16", dp: int = 1,
    mp: int = 1,
) -> int:
    """Padded dense-R footprint — the auto-dispatch gate's input.
    `dp` > 1 pads rows (and `mp` > 1 columns) to whole per-device slabs
    (stage_dense does)."""
    from predictionio_tpu.ops.dense import BYTES_PER_CELL

    n_u_p, n_i_p = _padded_dims(n_users, n_items, dp, mp)
    return n_u_p * n_i_p * BYTES_PER_CELL.get(dense_dtype, 2)


# pairs counted per np.bincount call in _degrees
_DEGREE_CHUNK = 1 << 20


def _degrees(rows, cols, n_users: int, n_items: int):
    """(user_deg, item_deg): how many pairs name each user and each item,
    as float32 vectors of the table sizes — one `np.bincount` a side, in
    chunks so the intp copy bincount makes of int32 ids stays in cache.
    Exact below 2**24 pairs an id; past that float32 rounds the true
    count, where accumulating 1.0 in float32 would stick at 16,777,216.
    An id outside its table raises, as indexing the vector would."""
    out = []
    for ids, n in ((rows, n_users), (cols, n_items)):
        ids = np.asarray(ids)
        deg = np.zeros(n, np.int64)
        for i in range(0, len(ids), _DEGREE_CHUNK):
            part = np.bincount(ids[i : i + _DEGREE_CHUNK], minlength=n)
            if len(part) != n:
                raise IndexError(
                    f"index {len(part) - 1} is out of bounds for a table "
                    f"of {n} rows"
                )
            deg += part
        out.append(deg.astype(np.float32))
    return tuple(out)


@dataclass(frozen=True)
class GroupedPairs:
    """A job's unique pairs in the order `ops.dense.densify` builds the
    matrix from: grouped by row block, ascending by cell inside a block.
    What `_group_unique_pairs` keeps of the job's one sort."""

    #: (E,) int32 — a pair's cell inside its row block,
    #: (row mod ROW_BLOCK) · n_cols_p + col
    offsets: np.ndarray
    #: (E,) storage dtype — what the matrix holds at that cell
    values: np.ndarray
    #: (n_blocks + 1,) int32 — block b's pairs are [starts[b], starts[b+1])
    starts: np.ndarray
    #: (n_rows_p, n_cols_p, dense_dtype, scale) the pairs were grouped
    #: for: a staging that pads, stores or scales otherwise groups its own
    grouped_for: tuple


@dataclass(frozen=True)
class DenseGate:
    """`dense_eligible`'s answer and what it learned on the way, so that
    `stage_dense` neither scans the ratings for their int8 scale nor
    sorts the pairs again. Truthy when the dense path may run."""

    #: "eligible", or the condition that refused: env_off, rank,
    #: multi_process, few_edges, bytes, explicit_zero, duplicate_pairs
    verdict: str
    #: storage dtype the byte budget was reckoned with; None when an
    #: earlier condition refused
    dense_dtype: Optional[str] = None
    #: True when `int8_scale` holds `ops.dense.int8_scale(vals)` (None
    #: there means "not exactly quantizable")
    scale_known: bool = False
    int8_scale: Optional[float] = None
    #: the pairs as the uniqueness sort left them; set when eligible
    pairs: Optional[GroupedPairs] = field(
        default=None, compare=False, repr=False
    )

    def __bool__(self) -> bool:
        return self.verdict == "eligible"


# pairs a step of _group_unique_pairs' linear passes, so that a step's
# temporaries stay in cache
_PAIR_CHUNK = 1 << 18


def _group_unique_pairs(
    rows, cols, vals, n_rows_p: int, n_cols_p: int, dense_dtype: str,
    scale: float,
) -> Optional[GroupedPairs]:
    """The job's one sort. One int64 key a pair, built and sorted in ONE
    buffer: the high bits are the pair's place in row-major order of the
    matrix — its row block, then its cell inside the block — and the low
    bits what the device stores there: the int8 code (8 bits), the bf16
    bits (16) or, for f32 storage, the pair's own index, by which the
    values are gathered. Sorted, the keys are grouped by row block and
    two pairs of one cell are neighbours: None when there are such, else
    the pairs as `ops.dense.densify` takes them."""
    from predictionio_tpu.ops.dense import (
        MAX_DENSE_COLS,
        ROW_BLOCK,
        storage_dtype,
    )

    n = len(rows)
    st = np.dtype(storage_dtype(dense_dtype))
    by_index = st.itemsize == 4
    n_blocks = n_rows_p // ROW_BLOCK
    row_shift = ROW_BLOCK.bit_length() - 1
    cells = ROW_BLOCK * n_cols_p  # of a block
    off_bits = (cells - 1).bit_length()
    low_bits = max(1, (n - 1).bit_length()) if by_index else 8 * st.itemsize
    if (
        n_cols_p >= MAX_DENSE_COLS
        or n_blocks.bit_length() + off_bits + low_bits > 63
    ):
        raise ValueError(
            f"a {n_rows_p} x {n_cols_p} matrix of {n} pairs does not fit "
            "the dense path's pair key"
        )
    # the three phases are spans of their own (ISSUE 37), children of
    # whichever span groups the pairs (`als.train.dense_eligible`; a bare
    # `stage_dense`'s `als.stage.host_prep`): the key's build, the sort,
    # the pass out of the sorted keys
    with _spans.span("als.train.pair_key"):
        key = np.empty(n, np.int64)
        for i in range(0, n, _PAIR_CHUNK):
            j = min(i + _PAIR_CHUNK, n)
            k = key[i:j]
            row = rows[i:j] & (ROW_BLOCK - 1)  # ROW_BLOCK is a power of two
            row *= n_cols_p
            row += cols[i:j]
            k[:] = rows[i:j] >> row_shift
            k <<= off_bits
            k |= row
            k <<= low_bits
            if by_index:
                k |= np.arange(i, j)
            elif st.itemsize == 1:
                k |= np.rint(
                    vals[i:j] * np.float32(scale)
                ).astype(st).view(np.uint8)
            else:
                k |= vals[i:j].astype(st).view(np.uint16)
    with _spans.span("als.train.pair_sort"):
        key.sort()
    with _spans.span("als.train.pair_group"):
        offsets = np.empty(n, np.int32)
        low = np.empty(n, np.int64 if by_index else f"u{st.itemsize}")
        off_mask, low_mask = (1 << off_bits) - 1, (1 << low_bits) - 1
        last = -1
        for i in range(0, n, _PAIR_CHUNK):
            k = key[i : i + _PAIR_CHUNK]
            cell = k >> low_bits
            if cell[0] == last or (cell[1:] == cell[:-1]).any():
                return None
            last = cell[-1]
            offsets[i : i + _PAIR_CHUNK] = cell & off_mask
            low[i : i + _PAIR_CHUNK] = k & low_mask
        starts = np.searchsorted(
            key,
            np.arange(n_blocks + 1, dtype=np.int64) << (off_bits + low_bits),
        ).astype(np.int32)
    return GroupedPairs(
        offsets, vals[low] if by_index else low.view(st), starts,
        (n_rows_p, n_cols_p, dense_dtype, float(scale)),
    )


def dense_eligible(
    rows: np.ndarray,
    cols: np.ndarray,
    vals: np.ndarray,
    n_users: int,
    n_items: int,
    params: "ALSParams",
    mesh=None,
    dense_dtype: str = "bf16",
) -> DenseGate:
    """Gate for the dense-W fast path, on the host.

    Requires: env not opting out, rank within the gram-solver bound,
    single-process execution when a mesh is given (the shard_map'd dense
    train row-shards R over dp; multi-host R staging is not wired, so
    multi-host falls back to the windowed path), the padded matrix
    within the HBM budget, unique (user, item) pairs (a dense cell can
    hold one rating; duplicate edges are summed by the windowed path, so
    dup data falls back to preserve semantics), and — explicit mode only
    — no zero-valued ratings (a dense zero must mean "unobserved").
    Auto mode also requires DENSE_AUTO_MIN_EDGES so small (test-scale)
    trains keep their f32-exact windowed numerics unless PIO_DENSE_ALS=1
    opts in. The conditions are tested cheapest first: the pair keys,
    the one sort of the job, are built only when nothing else refused,
    and an eligible gate keeps what the sort grouped (`gate.pairs`) for
    `stage_dense`."""
    with _spans.span("als.train.dense_eligible") as sp:
        gate = _dense_gate(
            rows, cols, vals, n_users, n_items, params, mesh, dense_dtype
        )
        sp.attrs.update(
            pairs=len(rows), verdict=gate.verdict,
            dense_dtype=gate.dense_dtype, int8_scale=gate.int8_scale,
            sort_kept=gate.pairs is not None,
        )
    if gate.verdict == "duplicate_pairs":
        logging.getLogger(__name__).info(
            "dense ALS path skipped: duplicate (user, item) pairs"
        )
    return gate


def _dense_gate(
    rows, cols, vals, n_users, n_items, params, mesh, dense_dtype
) -> DenseGate:
    from predictionio_tpu.ops.dense import MAX_DENSE_COLS, int8_scale

    env = env_str("PIO_DENSE_ALS").strip()
    if env == "0":
        return DenseGate("env_off")
    if params.rank > GRAM_SOLVER_MAX_RANK:
        return DenseGate("rank")
    if mesh is not None and jax.process_count() > 1:
        return DenseGate("multi_process")
    if env != "1" and len(rows) < DENSE_AUTO_MIN_EDGES:
        return DenseGate("few_edges")
    known = dict(dense_dtype=dense_dtype)
    if dense_dtype == "bf16":  # the default: predict what auto picks
        # a span of its own since the gate's span has children (ISSUE
        # 37): what a parent does outside them counts as unattributed
        with _spans.span("als.train.int8_scale"):
            s_q = int8_scale(vals)
        known = dict(
            dense_dtype="bf16" if s_q is None else "int8",
            scale_known=True, int8_scale=s_q,
        )
    dp = mp = 1
    if mesh is not None and getattr(mesh, "devices", None) is not None:
        from predictionio_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS

        dp = int(mesh.shape.get(DATA_AXIS, 1))
        mp = int(mesh.shape.get(MODEL_AXIS, 1))
    n_u_p, n_i_p = _padded_dims(n_users, n_items, dp, mp)
    # the second condition: densify addresses a row block's cells in int32
    if dense_matrix_bytes(
        n_users, n_items, known["dense_dtype"], dp=dp, mp=mp
    ) > env_int("PIO_DENSE_ALS_BYTES") or n_i_p >= MAX_DENSE_COLS:
        return DenseGate("bytes", **known)
    if not params.implicit_prefs and np.any(vals == 0.0):
        return DenseGate("explicit_zero", **known)
    pairs = _group_unique_pairs(
        rows, cols, vals, n_u_p, n_i_p,
        known["dense_dtype"], known.get("int8_scale") or 1.0,
    )
    if pairs is None:
        return DenseGate("duplicate_pairs", **known)
    return DenseGate("eligible", pairs=pairs, **known)


def _dense_pallas_mode():
    from predictionio_tpu.ops import dense_pallas

    return dense_pallas.resolve_mode("auto")


def stage_dense(
    rows, cols, vals, n_users, n_items, params,
    user_deg=None, item_deg=None, init_factors=None,
    dense_dtype: str = "auto",
    mesh=None,
    gate: Optional[DenseGate] = None,
) -> StagedDenseTrain:
    """Stage the dense-path train: pad dims to the block quanta, group
    the pairs by row block, push them once (an int32 offset and a stored
    value a pair), build the matrix ON DEVICE a row block at a time (it
    never crosses the host link), and keep it resident.

    `gate` is `dense_eligible`'s answer for these same ratings, and
    `user_deg` / `item_deg` their degrees: what the caller has already
    computed — the int8 scale, the pairs its sort grouped — is taken
    over, what it has not is computed here by the same functions. The
    pairs must be unique.

    dense_dtype "auto" prefers int8 storage when every rating is exactly
    representable as round(r·s) for a small scale s (ML-style ratings
    are) — half the footprint and HBM stream of bf16, with block-local
    dequantization; otherwise bf16. "f32" is the exactness mode tests
    compare against the windowed path with."""
    from predictionio_tpu.ops import dense as dense_ops

    with _spans.span("als.stage.host_prep") as prep_sp:
        rows = np.asarray(rows, dtype=np.int32)
        cols = np.asarray(cols, dtype=np.int32)
        vals = np.asarray(vals, dtype=np.float32)
        scale = 1.0
        scale_reused = False
        if dense_dtype in ("auto", "int8"):
            scale_reused = gate is not None and gate.scale_known
            s_q = (
                gate.int8_scale if scale_reused
                else dense_ops.int8_scale(vals)
            )
            if s_q is not None:
                dense_dtype, scale = "int8", s_q
            elif dense_dtype == "int8":
                raise ValueError(
                    "dense_dtype='int8' but ratings are not exactly int8-"
                    "quantizable; use 'bf16' or 'auto'"
                )
            else:
                dense_dtype = "bf16"
        dp = mp = 1
        if mesh is not None and mesh.devices.size > 1:
            from predictionio_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS

            dp = int(mesh.shape.get(DATA_AXIS, 1))
            mp = int(mesh.shape.get(MODEL_AXIS, 1))
        n_u_p, n_i_p = _padded_dims(n_users, n_items, dp, mp)
        grouped_for = (n_u_p, n_i_p, dense_dtype, float(scale))
        pairs = gate.pairs if gate is not None else None
        pairs_reused = (
            pairs is not None
            and len(pairs.offsets) == len(rows)
            and pairs.grouped_for == grouped_for
        )
        if not pairs_reused:
            pairs = _group_unique_pairs(rows, cols, vals, *grouped_for)
            if pairs is None:
                raise ValueError(
                    "the dense path needs unique (user, item) pairs"
                )
        prep_sp.attrs.update(
            int8_scale_reused=scale_reused,
            pairs_grouped_reused=pairs_reused,
            degrees_reused=user_deg is not None and item_deg is not None,
        )
        if user_deg is None or item_deg is None:
            user_deg, item_deg = _degrees(rows, cols, n_users, n_items)

        def pad_deg(deg, n_padded):
            out = np.full(n_padded, -1.0, np.float32)  # -1 marks padding
            out[: len(deg)] = deg
            return out

        uf0 = itf0 = None
        if init_factors is not None:
            uf_in = np.asarray(init_factors[0], np.float32)
            itf_in = np.asarray(init_factors[1], np.float32)
            if uf_in.shape != (n_users, params.rank) or itf_in.shape != (
                n_items, params.rank,
            ):
                raise ValueError(
                    "init_factors shapes do not match (n_users/n_items, rank)"
                )
            uf0 = np.zeros((n_u_p, params.rank), np.float32)
            uf0[:n_users] = uf_in
            itf0 = np.zeros((n_i_p, params.rank), np.float32)
            itf0[:n_items] = itf_in

    sharded = mesh is not None and mesh.devices.size > 1
    if sharded:
        from jax.sharding import NamedSharding, PartitionSpec as P

        from predictionio_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS

        row_sh = NamedSharding(mesh, P(DATA_AXIS, None))
        vec_sh = NamedSharding(mesh, P(DATA_AXIS))
        rep = NamedSharding(mesh, P())
        if mp > 1:
            # model-axis sharding (ISSUE 10): R 2-D block-sharded, item
            # degree/factors row-sharded over mp — no device holds a
            # full factor matrix
            r_sh = NamedSharding(mesh, P(DATA_AXIS, MODEL_AXIS))
            ideg_sh = NamedSharding(mesh, P(MODEL_AXIS))
            itf_sh = NamedSharding(mesh, P(MODEL_AXIS, None))
        else:
            r_sh, ideg_sh, itf_sh = row_sh, rep, rep
    else:
        r_sh = vec_sh = ideg_sh = row_sh = itf_sh = None

    # staging is asynchronous: each span waits for what it started, so
    # transfer and densify are their own times and not part of the train's
    with _spans.span("als.stage.transfer") as xfer_sp:
        grouped = tuple(
            jnp.asarray(a)
            for a in (pairs.offsets, pairs.values, pairs.starts)
        )
        rest = (
            jax.device_put(pad_deg(user_deg, n_u_p), vec_sh),
            jax.device_put(pad_deg(item_deg, n_i_p), ideg_sh),
            jax.device_put(uf0, row_sh) if uf0 is not None else None,
            jax.device_put(itf0, itf_sh) if itf0 is not None else None,
        )
        jax.block_until_ready((grouped, rest))
        xfer_sp.attrs["bytes"] = sum(
            a.nbytes for a in grouped + rest if a is not None
        )
    with _spans.span("als.stage.densify") as dens_sp:
        dead = dense_ops.dead_slots(pairs.starts)
        dens_sp.attrs.update(
            blocks=len(pairs.starts) - 1, pairs=len(pairs.offsets),
            dead_slots=round(dead / max(1, dead + len(pairs.offsets)), 4),
        )
        r = dense_ops.densify(*grouped, n_rows_p=n_u_p, n_cols_p=n_i_p)
        if sharded:
            r = jax.device_put(r, r_sh)
        jax.block_until_ready(r)
    device_args = (r,) + rest
    return StagedDenseTrain(
        device_args=device_args,
        static_kwargs=dict(
            rank=params.rank,
            iterations=params.iterations,
            implicit=params.implicit_prefs,
            lam=params.lambda_,
            alpha=params.alpha,
            cg_iterations=params.cg_iterations,
            seed=params.seed,
            dense_dtype=dense_dtype,
            scale=scale,
            # resolved OUTSIDE the jit; the grid (vmap) and sharded
            # (shard_map) variants exclude the kernel for now — pop it
            pallas_mode=(
                None
                if (mesh is not None and mesh.devices.size > 1)
                else _dense_pallas_mode()
            ),
            mesh=mesh if (mesh is not None and mesh.devices.size > 1) else None,
        ),
        n_users=n_users,
        n_items=n_items,
    )


def _train_dense(
    rows, cols, vals, n_users, n_items, params,
    user_deg, item_deg, user_vocab, item_vocab, init_factors,
    dense_dtype: str = "auto",
    mesh=None,
    gate: Optional[DenseGate] = None,
) -> "ALSFactors":
    staged = stage_dense(
        rows, cols, vals, n_users, n_items, params,
        user_deg=user_deg, item_deg=item_deg, init_factors=init_factors,
        dense_dtype=dense_dtype, mesh=mesh, gate=gate,
    )
    uf, itf = staged.factors(*staged.run())
    return ALSFactors(
        user_factors=uf,
        item_factors=itf,
        user_vocab=user_vocab or BiMap({}),
        item_vocab=item_vocab or BiMap({}),
        params=params,
    )


# ---------------------------------------------------------------------------
# Core solver — scatter path (rank > 32 matrix-free CG, and meshes)
# ---------------------------------------------------------------------------


def _half_step_implicit(
    fixed: jax.Array,  # (N_fixed, K) — e.g. item factors when solving users
    src_idx: jax.Array,  # (E,) — edge rows into `fixed`
    dst_idx: jax.Array,  # (E,) — edge rows being solved (sorted)
    conf: jax.Array,  # (E,) confidence c = 1 + alpha*|r|
    pref: jax.Array,  # (E,) preference p = 1[r > 0] (MLlib trainImplicit)
    valid: jax.Array,  # (E,) 1.0 real edge / 0.0 padding
    x0: jax.Array,  # (N_dst, K) warm start
    lam: float,
    cg_iterations: int,
    n_chunks: int = 1,
) -> jax.Array:
    n_dst, k = x0.shape
    gram = f32_gram(fixed)  # (K, K)
    b = chunked_weighted_edge_sum(
        fixed, src_idx, dst_idx, conf * pref * valid, n_dst, n_chunks
    )

    if k <= GRAM_SOLVER_MAX_RANK:
        # explicit per-row operator: ONE edge pass builds all Σ(c-1)yyᵀ
        # corrections; CG then runs on the dense (N, K, K) batch with no
        # further edge traffic (2·cg_iterations fewer HBM sweeps)
        corr = chunked_gram_edge_sum(
            fixed, src_idx, dst_idx, (conf - 1.0) * valid, n_dst, n_chunks
        ).reshape(n_dst, k, k)
        a = corr + gram[None, :, :] + lam * jnp.eye(k, dtype=jnp.float32)

        def matvec(v):
            return jnp.einsum("nij,nj->ni", a, v)

        return batched_cg(matvec, b, x0, cg_iterations)

    def matvec(v):
        base = v @ gram + lam * v
        # (c-1) is already 0 for pads (r=0), but multiply by `valid` so
        # padding is inert regardless of alpha/rating conventions
        corr = chunked_edge_matvec(
            fixed, v, src_idx, dst_idx, (conf - 1.0) * valid, n_dst, n_chunks
        )
        return base + corr

    return batched_cg(matvec, b, x0, cg_iterations)


def _half_step_explicit(
    fixed: jax.Array,
    src_idx: jax.Array,
    dst_idx: jax.Array,
    ratings: jax.Array,
    valid: jax.Array,  # (E,) 1.0 real edge / 0.0 padding
    degree: jax.Array,  # (N_dst,) observation counts for ALS-WR scaling
    x0: jax.Array,
    lam: float,
    cg_iterations: int,
    n_chunks: int = 1,
) -> jax.Array:
    n_dst, k = x0.shape
    b = chunked_weighted_edge_sum(
        fixed, src_idx, dst_idx, ratings * valid, n_dst, n_chunks
    )
    reg = lam * jnp.maximum(degree, 1.0)  # ALS-WR per-row scaling

    if k <= GRAM_SOLVER_MAX_RANK:
        obs = chunked_gram_edge_sum(
            fixed, src_idx, dst_idx, valid, n_dst, n_chunks
        ).reshape(n_dst, k, k)
        a = obs + reg[:, None, None] * jnp.eye(k, dtype=jnp.float32)

        def matvec(v):
            return jnp.einsum("nij,nj->ni", a, v)

        return batched_cg(matvec, b, x0, cg_iterations)

    def matvec(v):
        base = reg[:, None] * v
        obs = chunked_edge_matvec(
            fixed, v, src_idx, dst_idx, valid, n_dst, n_chunks
        )
        return base + obs

    return batched_cg(matvec, b, x0, cg_iterations)


@partial(
    jax.jit,
    static_argnames=(
        "n_user_windows", "n_item_windows", "rank", "iterations", "implicit",
        "cg_iterations", "pallas_mode", "mesh",
    ),
)
def _train_jit_windowed(
    u_src, u_val, u_ok, u_loc, u_bwin,  # user-side plan (solving users)
    i_src, i_val, i_ok, i_loc, i_bwin,  # item-side plan (solving items)
    user_deg, item_deg,
    uf0=None, itf0=None,
    *,
    n_user_windows: int,
    n_item_windows: int,
    rank: int,
    iterations: int,
    implicit: bool,
    lam: float,
    alpha: float,
    cg_iterations: int,
    seed: int,
    pallas_mode: Optional[str] = None,
    mesh: Optional[jax.sharding.Mesh] = None,
):
    """Whole alternating loop on the windowed (scatter-free) path.

    Factor matrices are window-padded; pad rows start exactly zero and CG
    freezes them at zero (b=0, x0=0 ⇒ r0=0), so they never contaminate
    the fixed-side gram.

    With a mesh, chunk arrays arrive sharded part-major over dp (see
    stage_windowed); factors are row-sharded over mp (replicated when
    mp == 1) and each edge pass ends in one GSPMD-inserted all-reduce of
    the window sums."""
    from predictionio_tpu.ops.windowed import WINDOW_ROWS

    if mesh is not None and mesh.devices.size > 1:
        from predictionio_tpu.parallel.mesh import (
            MODEL_AXIS,
            factor_sharding,
            replicated,
        )

        # the pallas kernel no longer downgrades under a mesh: P > 1
        # runs it shard_map'd over dp (ops/windowed.py) — only pass the
        # mesh handle through so the edge pass can build the shard_map
        sh = (
            factor_sharding(mesh)
            if mesh.shape.get(MODEL_AXIS, 1) > 1
            else replicated(mesh)
        )

        def shard_factors(f):
            return jax.lax.with_sharding_constraint(f, sh)

        half_step_mesh = mesh
    else:
        half_step_mesh = None

        def shard_factors(f):
            return f

    n_users_p = n_user_windows * WINDOW_ROWS
    n_items_p = n_item_windows * WINDOW_ROWS
    if uf0 is not None and itf0 is not None:
        uf, itf = shard_factors(uf0), shard_factors(itf0)
    else:
        ku, ki = jax.random.split(jax.random.PRNGKey(seed))
        # partitionable threefry across ALL train paths: draws are
        # shape-stable per element, so differently-padded paths (dense
        # vs windowed vs sharded slabs) agree on the real rows
        with jax.threefry_partitionable(True):
            uf = (
                jax.random.normal(ku, (n_users_p, rank), jnp.float32)
                / jnp.sqrt(rank)
            )
            itf = (
                jax.random.normal(ki, (n_items_p, rank), jnp.float32)
                / jnp.sqrt(rank)
            )
        # zero the window-padding rows so they stay exactly zero under CG
        uf = shard_factors(uf * (user_deg >= 0)[:, None])
        itf = shard_factors(itf * (item_deg >= 0)[:, None])

    def body(_, fs):
        uf, itf = fs
        uf = shard_factors(_half_step_windowed(
            itf, u_src, u_val, u_ok, u_loc, u_bwin, user_deg, uf,
            n_windows=n_user_windows, implicit=implicit, lam=lam,
            alpha=alpha, cg_iterations=cg_iterations,
            pallas_mode=pallas_mode, mesh=half_step_mesh,
        ))
        itf = shard_factors(_half_step_windowed(
            uf, i_src, i_val, i_ok, i_loc, i_bwin, item_deg, itf,
            n_windows=n_item_windows, implicit=implicit, lam=lam,
            alpha=alpha, cg_iterations=cg_iterations,
            pallas_mode=pallas_mode, mesh=half_step_mesh,
        ))
        return uf, itf

    return jax.lax.fori_loop(0, iterations, body, (uf, itf))


_train_jit_windowed = _devprof.instrument(
    "als.train_windowed", _train_jit_windowed, scale_by="iterations"
)


@partial(
    jax.jit,
    static_argnames=(
        "n_user_windows", "n_item_windows", "rank", "iterations", "implicit",
        "cg_iterations", "pallas_mode",
    ),
)
def _train_jit_windowed_grid(
    u_src, u_val, u_ok, u_loc, u_bwin,
    i_src, i_val, i_ok, i_loc, i_bwin,
    user_deg, item_deg,
    lams, alphas,  # (G,) f32 — the hyperparameter grid axis
    *,
    n_user_windows: int,
    n_item_windows: int,
    rank: int,
    iterations: int,
    implicit: bool,
    cg_iterations: int,
    seed: int,
    pallas_mode: Optional[str] = None,
):
    """N-point (λ, α) grid trained as ONE device program (VERDICT r3 #6).

    The staged edge plan is hyperparameter-independent at fixed rank, so
    every grid point shares it (vmap broadcasts — no G× edge copies in
    HBM); the alternating loops and their CG solves run batched over the
    grid axis. The Pallas edge kernel vmaps too (VERDICT r4 #2): the
    per-chunk kernel has no cross-grid-step state, so pallas_call's
    grid-prepending batching rule is sound for it — verified against
    per-point runs in tests/test_windowed_pallas.py."""

    def one(lam, alpha):
        return _train_jit_windowed(
            u_src, u_val, u_ok, u_loc, u_bwin,
            i_src, i_val, i_ok, i_loc, i_bwin,
            user_deg, item_deg,
            n_user_windows=n_user_windows,
            n_item_windows=n_item_windows,
            rank=rank, iterations=iterations, implicit=implicit,
            lam=lam, alpha=alpha, cg_iterations=cg_iterations, seed=seed,
            pallas_mode=pallas_mode, mesh=None,
        )

    return jax.vmap(one)(lams, alphas)


_train_jit_windowed_grid = _devprof.instrument(
    "als.train_windowed_grid", _train_jit_windowed_grid,
    scale_by="iterations",
)


def train_grid(
    rows: np.ndarray,
    cols: np.ndarray,
    vals: np.ndarray,
    n_users: int,
    n_items: int,
    params_list: Sequence[ALSParams],
    user_vocab: Optional[BiMap] = None,
    item_vocab: Optional[BiMap] = None,
) -> list["ALSFactors"]:
    """Train an ALS hyperparameter grid sharing staged training data.

    λ/α vary FREELY within one device program (vmapped solves); rank /
    iterations / cg_iterations / implicit / seed set program SHAPE, so
    grid points are grouped by that signature and each group runs as one
    batched launch — but every group shares ONE staging, because both
    staged forms are rank-independent (the WindowPlan blocks by
    destination row only; the dense rating matrix doesn't know about
    factors at all). A rank×λ grid therefore costs G_rank launches over
    one staged edge set instead of G_rank·G_λ serial train+stagings
    (VERDICT r4 #7; reference: the strictly serial MetricEvaluator grid,
    core/.../controller/Engine.scala:758-764)."""
    for p in params_list:
        if p.rank > GRAM_SOLVER_MAX_RANK:
            raise ValueError(
                f"train_grid supports rank <= {GRAM_SOLVER_MAX_RANK}"
            )
    rows = np.asarray(rows, dtype=np.int32)
    cols = np.asarray(cols, dtype=np.int32)
    vals = np.asarray(vals, dtype=np.float32)

    # group by program-shape signature, preserving input positions
    groups: dict[tuple, list[int]] = {}
    for i, p in enumerate(params_list):
        key = (
            p.rank, p.iterations, p.cg_iterations, p.implicit_prefs, p.seed
        )
        groups.setdefault(key, []).append(i)

    base = params_list[0]
    # data-dependent eligibility (pair uniqueness, quantization, budget)
    # is identical for every group — check once against base, then only
    # the cheap per-group condition (explicit mode forbids zero ratings)
    gate = dense_eligible(rows, cols, vals, n_users, n_items, base)
    use_dense = bool(gate)
    if use_dense and not all(
        params_list[ix[0]].implicit_prefs for ix in groups.values()
    ):
        use_dense = not np.any(vals == 0.0)
    staged_d = staged_w = None
    if use_dense:
        # ONE device rating matrix serves every grid point and every
        # rank group (vmap broadcasts; R has no rank axis)
        staged_d = stage_dense(
            rows, cols, vals, n_users, n_items, base, gate=gate
        )
    else:
        staged_w = stage_windowed(rows, cols, vals, n_users, n_items, base)

    out: list[Optional[ALSFactors]] = [None] * len(params_list)
    for key, idxs in groups.items():
        rank, iterations, cg_iterations, implicit, seed = key
        lams = jnp.asarray(
            [params_list[i].lambda_ for i in idxs], jnp.float32
        )
        alphas = jnp.asarray(
            [params_list[i].alpha for i in idxs], jnp.float32
        )
        if staged_d is not None:
            kwargs = dict(staged_d.static_kwargs)
            kwargs.pop("lam"), kwargs.pop("alpha")
            kwargs.pop("mesh", None)  # grids run single-device
            kwargs.pop("pallas_mode", None)  # vmap excluded for now
            kwargs.update(
                rank=rank, iterations=iterations,
                cg_iterations=cg_iterations, implicit=implicit, seed=seed,
            )
            ufs, itfs = _train_jit_dense_grid(
                *staged_d.device_args[:3], lams, alphas, **kwargs
            )
        else:
            kwargs = dict(staged_w.static_kwargs)
            for grid_axis_or_unsupported in ("lam", "alpha", "mesh"):
                kwargs.pop(grid_axis_or_unsupported)
            kwargs.update(
                rank=rank, iterations=iterations,
                cg_iterations=cg_iterations, implicit=implicit, seed=seed,
            )
            ufs, itfs = _train_jit_windowed_grid(
                *staged_w.device_args[:12], lams, alphas, **kwargs
            )
        ufs, itfs = np.asarray(ufs), np.asarray(itfs)
        for g, i in enumerate(idxs):
            out[i] = ALSFactors(
                user_factors=ufs[g][:n_users],
                item_factors=itfs[g][:n_items],
                user_vocab=user_vocab or BiMap({}),
                item_vocab=item_vocab or BiMap({}),
                params=params_list[i],
            )
    return out  # type: ignore[return-value]


@partial(
    jax.jit,
    static_argnames=(
        "n_users", "n_items", "rank", "iterations", "implicit", "cg_iterations",
        "mesh", "n_chunks",
    ),
)
def _train_jit(
    u_src: jax.Array,  # (E,) item idx, sorted by user
    u_dst: jax.Array,  # (E,) user idx, sorted
    u_val: jax.Array,  # (E,)
    u_ok: jax.Array,  # (E,) 1.0 real / 0.0 pad
    i_src: jax.Array,  # (E,) user idx, sorted by item
    i_dst: jax.Array,  # (E,) item idx, sorted
    i_val: jax.Array,  # (E,)
    i_ok: jax.Array,  # (E,)
    user_deg: jax.Array,
    item_deg: jax.Array,
    uf0: Optional[jax.Array] = None,  # warm start (resume/checkpoint)
    itf0: Optional[jax.Array] = None,
    *,
    n_users: int,
    n_items: int,
    rank: int,
    iterations: int,
    implicit: bool,
    lam: float,
    alpha: float,
    cg_iterations: int,
    seed: int,
    mesh: Optional[jax.sharding.Mesh] = None,
    n_chunks: int = 1,
):
    if mesh is not None:
        from predictionio_tpu.parallel.mesh import MODEL_AXIS, factor_sharding, replicated

        sh = (
            factor_sharding(mesh)
            if mesh.shape.get(MODEL_AXIS, 1) > 1
            else replicated(mesh)
        )

        def shard_factors(f):
            return jax.lax.with_sharding_constraint(f, sh)

    else:

        def shard_factors(f):
            return f

    if uf0 is not None and itf0 is not None:
        uf = shard_factors(uf0)
        itf = shard_factors(itf0)
    else:
        ku, ki = jax.random.split(jax.random.PRNGKey(seed))
        # signed gaussian init scaled by 1/sqrt(rank); an all-positive init
        # (as some ALS impls use) starts near rank-1 and converges far slower
        with jax.threefry_partitionable(True):
            uf = shard_factors(
                jax.random.normal(ku, (n_users, rank), jnp.float32)
                / jnp.sqrt(rank)
            )
            itf = shard_factors(
                jax.random.normal(ki, (n_items, rank), jnp.float32)
                / jnp.sqrt(rank)
            )

    if implicit:
        # MLlib trainImplicit semantics (Hu-Koren-Volinsky with signed
        # feedback): confidence from |r| so a dislike (r<0) still raises
        # confidence, preference 1 only for r>0 — a disliked item is pulled
        # toward 0 HARDER than a never-seen one, and c stays positive so
        # the normal-equation operator is always SPD for CG.
        u_w = 1.0 + alpha * jnp.abs(u_val)
        i_w = 1.0 + alpha * jnp.abs(i_val)
        u_p = (u_val > 0).astype(jnp.float32)
        i_p = (i_val > 0).astype(jnp.float32)

        def body(_, fs):
            uf, itf = fs
            uf = shard_factors(_half_step_implicit(
                itf, u_src, u_dst, u_w, u_p, u_ok, uf, lam, cg_iterations,
                n_chunks,
            ))
            itf = shard_factors(_half_step_implicit(
                uf, i_src, i_dst, i_w, i_p, i_ok, itf, lam, cg_iterations,
                n_chunks,
            ))
            return uf, itf

    else:

        def body(_, fs):
            uf, itf = fs
            uf = shard_factors(_half_step_explicit(
                itf, u_src, u_dst, u_val, u_ok, user_deg, uf, lam,
                cg_iterations, n_chunks,
            ))
            itf = shard_factors(_half_step_explicit(
                uf, i_src, i_dst, i_val, i_ok, item_deg, itf, lam,
                cg_iterations, n_chunks,
            ))
            return uf, itf

    uf, itf = jax.lax.fori_loop(0, iterations, body, (uf, itf))
    return uf, itf


_train_jit = _devprof.instrument(
    "als.train_edge", _train_jit, scale_by="iterations"
)


def train(
    rows: np.ndarray,  # (E,) user indices
    cols: np.ndarray,  # (E,) item indices
    vals: np.ndarray,  # (E,) ratings / interaction weights
    n_users: int,
    n_items: int,
    params: ALSParams = ALSParams(),
    user_vocab: Optional[BiMap] = None,
    item_vocab: Optional[BiMap] = None,
    mesh: Optional[jax.sharding.Mesh] = None,
    init_factors: Optional[tuple[np.ndarray, np.ndarray]] = None,
) -> ALSFactors:
    """Train factors from a COO interaction list.

    `init_factors=(uf, itf)` warm-starts the alternating loop (checkpoint
    resume / incremental retrain); ALS iterations are memoryless in the
    factor state, so k resumed segments of m iterations reproduce one
    k·m-iteration run.

    When `mesh` is given, edge arrays are sharded over its first (data)
    axis and GSPMD inserts the ICI all-reduces for the segment sums;
    factor matrices are row-sharded over the model axis when it has more
    than one device, else replicated.
    """
    with _spans.span("als.train.degrees"):
        rows = np.asarray(rows, dtype=np.int32)
        cols = np.asarray(cols, dtype=np.int32)
        vals = np.asarray(vals, dtype=np.float32)
        user_deg, item_deg = _degrees(rows, cols, n_users, n_items)

    gate = dense_eligible(rows, cols, vals, n_users, n_items, params, mesh)
    if gate:
        return _train_dense(
            rows, cols, vals, n_users, n_items, params,
            user_deg, item_deg, user_vocab, item_vocab, init_factors,
            mesh=mesh, gate=gate,
        )

    if params.rank <= GRAM_SOLVER_MAX_RANK:
        return _train_windowed(
            rows, cols, vals, n_users, n_items, params,
            user_deg, item_deg, user_vocab, item_vocab, init_factors,
            mesh=mesh,
        )

    valid = np.ones(len(rows), np.float32)
    n_chunks = max(
        1, -(-len(rows) // max(1, params.edge_chunk_size))
    )
    # pad so the edge axis divides by n_chunks (and the mesh size when
    # sharded) — padded edges carry valid=0.0 and are inert in every term
    unit = n_chunks * (mesh.devices.size if mesh is not None else 1)
    pad = (-len(rows)) % unit
    if pad:
        rows = np.concatenate([rows, np.zeros(pad, np.int32)])
        cols = np.concatenate([cols, np.zeros(pad, np.int32)])
        vals = np.concatenate([vals, np.zeros(pad, np.float32)])
        valid = np.concatenate([valid, np.zeros(pad, np.float32)])

    by_user = np.argsort(rows, kind="stable")
    by_item = np.argsort(cols, kind="stable")

    uf0 = itf0 = None
    if init_factors is not None:
        uf0 = np.asarray(init_factors[0], np.float32)
        itf0 = np.asarray(init_factors[1], np.float32)
        if uf0.shape != (n_users, params.rank) or itf0.shape != (
            n_items, params.rank,
        ):
            raise ValueError(
                "init_factors shapes do not match (n_users/n_items, rank)"
            )
    args = (
        cols[by_user], rows[by_user], vals[by_user], valid[by_user],
        rows[by_item], cols[by_item], vals[by_item], valid[by_item],
        user_deg, item_deg, uf0, itf0,
    )
    kwargs = dict(
        n_users=n_users,
        n_items=n_items,
        rank=params.rank,
        iterations=params.iterations,
        implicit=params.implicit_prefs,
        lam=params.lambda_,
        alpha=params.alpha,
        cg_iterations=params.cg_iterations,
        seed=params.seed,
        n_chunks=n_chunks,
    )
    if mesh is not None:
        if jax.process_count() > 1:
            # multi-host: device_put cannot place onto other processes'
            # devices — stage through the loader seam instead. Every
            # process passes the identical full edge arrays; stage_rows
            # extracts this process's contiguous row block and assembles
            # the global sharded array (reference analogue: HBase
            # executor-partitioned reads, HBPEvents.scala:84-90).
            from predictionio_tpu.parallel.loader import (
                stage_replicated,
                stage_rows,
            )

            device_args = list(stage_rows(mesh, *args[:8])) + [
                stage_replicated(mesh, a) if a is not None else None
                for a in args[8:]
            ]
        else:
            from predictionio_tpu.parallel.mesh import edge_sharding, replicated

            edge_sh = edge_sharding(mesh)
            rep_sh = replicated(mesh)
            device_args = [
                jax.device_put(a, edge_sh) for a in args[:8]
            ] + [
                jax.device_put(a, rep_sh) if a is not None else None
                for a in args[8:]
            ]
        uf, itf = _run_program(_train_jit, *device_args, mesh=mesh, **kwargs)
    else:
        uf, itf = _run_program(_train_jit, *args, **kwargs)
    uf, itf = _copy_back(uf, itf, n_users, n_items)
    return ALSFactors(
        user_factors=uf,
        item_factors=itf,
        user_vocab=user_vocab or BiMap({}),
        item_vocab=item_vocab or BiMap({}),
        params=params,
    )


@dataclass
class StagedWindowedTrain:
    """A windowed-path train with all edge data staged on device.

    Built once per training set by `stage_windowed`; `run()` re-executes
    the compiled alternating loop with no further host→device traffic."""

    device_args: tuple
    static_kwargs: dict
    n_users: int
    n_items: int

    def run(self) -> tuple[jax.Array, jax.Array]:
        """One full train; returns window-padded device factor arrays."""
        return _run_program(
            _train_jit_windowed, *self.device_args, **self.static_kwargs
        )

    def factors(self, uf: jax.Array, itf: jax.Array) -> tuple[np.ndarray, np.ndarray]:
        return _copy_back(uf, itf, self.n_users, self.n_items)


def stage_windowed(
    rows, cols, vals, n_users, n_items, params,
    user_deg=None, item_deg=None, init_factors=None,
    mesh: Optional[jax.sharding.Mesh] = None,
) -> StagedWindowedTrain:
    """Host plan + device staging for the windowed (scatter-free) path.

    Host builds the two block plans (users-sorted and items-sorted) once —
    see ops/windowed.py — and pushes every edge array to device HBM.

    With a mesh, each plan is built with n_parts = |dp| contiguous block
    groups; chunk arrays land sharded part-major over dp (multi-host:
    each process stages only its contiguous slice of parts — the
    HBPEvents.scala:84-90 partitioned-read role). Degrees/init factors
    are replicated; mp row-sharding is applied inside the jit."""
    with _spans.span("als.stage.host_prep") as prep_sp:
        # single gate for both staging sharding and mesh pass-through: a
        # model-parallel-only mesh (dp=1, mp>1) still stages replicated
        # arrays but must reach the jit so mp row-sharding applies (ADVICE r4)
        use_mesh = mesh is not None and mesh.devices.size > 1
        n_parts = 1
        if use_mesh:
            from predictionio_tpu.parallel.mesh import DATA_AXIS

            n_parts = int(mesh.shape.get(DATA_AXIS, 1))
        prep_sp.attrs["degrees_reused"] = (
            user_deg is not None and item_deg is not None
        )
        if user_deg is None or item_deg is None:
            user_deg, item_deg = _degrees(rows, cols, n_users, n_items)
        by_user = np.argsort(rows, kind="stable")
        by_item = np.argsort(cols, kind="stable")
        plan_u = plan_windows(rows[by_user], n_users, n_parts)
        plan_i = plan_windows(cols[by_item], n_items, n_parts)

        def pad_deg(deg, n_padded):
            out = np.full(n_padded, -1.0, np.float32)  # -1 marks window padding
            out[: len(deg)] = deg
            return out

        uf0 = itf0 = None
        if init_factors is not None:
            uf_in = np.asarray(init_factors[0], np.float32)
            itf_in = np.asarray(init_factors[1], np.float32)
            if uf_in.shape != (n_users, params.rank) or itf_in.shape != (
                n_items, params.rank,
            ):
                raise ValueError(
                    "init_factors shapes do not match (n_users/n_items, rank)"
                )
            uf0 = np.zeros((plan_u.n_rows_padded, params.rank), np.float32)
            uf0[:n_users] = uf_in
            itf0 = np.zeros((plan_i.n_rows_padded, params.rank), np.float32)
            itf0[:n_items] = itf_in

        host_args = (
            plan_u.take(cols[by_user]),
            plan_u.take(vals[by_user]),
            plan_u.chunked_valid(),
            plan_u.chunked_local(),
            plan_u.block_window,
            plan_i.take(rows[by_item]),
            plan_i.take(vals[by_item]),
            plan_i.chunked_valid(),
            plan_i.chunked_local(),
            plan_i.block_window,
            pad_deg(user_deg, plan_u.n_rows_padded),
            pad_deg(item_deg, plan_i.n_rows_padded),
            uf0, itf0,
        )
    with _spans.span("als.stage.transfer") as xfer_sp:
        if use_mesh:
            from jax.sharding import NamedSharding, PartitionSpec as P

            from predictionio_tpu.parallel.mesh import DATA_AXIS

            n_procs = jax.process_count()
            p_idx = jax.process_index()

            def put(a):
                if a is None:
                    return None
                # chunk arrays (P, L, CB, B_E) and block_window (P*L*CB,)
                # shard their leading axis over dp; everything else
                # (degrees, init factors) is replicated. With dp == 1
                # (mp-only mesh) NOTHING is dp-sharded — the multi-process
                # slice below would otherwise compute shape[0] // n_procs
                # = 0 and hand GSPMD an empty local buffer
                sharded = n_parts > 1 and (
                    a.ndim == 4 or a.dtype == np.int32 and a.ndim == 1
                )
                spec = (
                    P(DATA_AXIS, *([None] * (a.ndim - 1))) if sharded else P()
                )
                sh = NamedSharding(mesh, spec)
                if n_procs > 1:
                    local = a
                    if sharded:
                        per = a.shape[0] // n_procs
                        local = a[p_idx * per : (p_idx + 1) * per]
                    return jax.make_array_from_process_local_data(
                        sh, local, a.shape
                    )
                return jax.device_put(a, sh)

            device_args = tuple(put(a) for a in host_args)
        else:
            device_args = tuple(
                jax.device_put(a) if a is not None else None for a in host_args
            )
        jax.block_until_ready(device_args)
        xfer_sp.attrs["bytes"] = sum(
            a.nbytes for a in host_args if a is not None
        )
    from predictionio_tpu.ops.windowed import resolve_pallas_mode

    return StagedWindowedTrain(
        device_args=device_args,
        static_kwargs=dict(
            n_user_windows=plan_u.n_windows,
            n_item_windows=plan_i.n_windows,
            rank=params.rank,
            iterations=params.iterations,
            implicit=params.implicit_prefs,
            lam=params.lambda_,
            alpha=params.alpha,
            cg_iterations=params.cg_iterations,
            seed=params.seed,
            # resolved OUTSIDE the jit so the trace cache keys on it
            pallas_mode=resolve_pallas_mode("auto"),
            mesh=mesh if use_mesh else None,
        ),
        n_users=n_users,
        n_items=n_items,
    )


def _train_windowed(
    rows, cols, vals, n_users, n_items, params,
    user_deg, item_deg, user_vocab, item_vocab, init_factors,
    mesh: Optional[jax.sharding.Mesh] = None,
) -> "ALSFactors":
    """Train on the windowed scatter-free path (single device or mesh)."""
    staged = stage_windowed(
        rows, cols, vals, n_users, n_items, params,
        user_deg=user_deg, item_deg=item_deg, init_factors=init_factors,
        mesh=mesh,
    )
    uf, itf = staged.factors(*staged.run())
    return ALSFactors(
        user_factors=uf,
        item_factors=itf,
        user_vocab=user_vocab or BiMap({}),
        item_vocab=item_vocab or BiMap({}),
        params=params,
    )


# ---------------------------------------------------------------------------
# Serving-side scoring
# ---------------------------------------------------------------------------
#
# Two generations coexist:
#
# - the PR-2 path (`recommend` + `_recommend_jit[_nomask]`): exact-width
#   f32 factor matrices, XLA two-step (scores matmul -> lax.top_k). Kept
#   for callers that serve straight off an ALSFactors.
# - the ISSUE-11 path (`stage_serving` + `recommend_serving`): a staged
#   `ServingFactors` whose item matrix is pad-aligned for the fused
#   Pallas recommend+top-k kernel (ops/recommend_pallas.py — one HBM
#   pass, no (B, I) score matrix), optionally int8-quantized per row
#   (half the factor stream; int8xint8->int32 scoring), with device-side
#   copy-on-write row publish for the online fold-in so a tick re-ships
#   only its dirty rows instead of a factor matrix.
#
# Donation note (measured, not assumed): the per-query programs' outputs
# ((B, k) values + indices) are strictly smaller than every input, so
# `donate_argnums` on the query-row/mask buffers has nothing to alias —
# XLA reports the donation unusable. The donation lever that IS real on
# this shape is the state-update path: `_set_rows_donated` aliases a
# grown factor table into its row-published successor during fold-in
# publish, and it only ever runs on a buffer this publish privately
# created (the COW copy readers never see), so swaps stay zero-drop.


@partial(jax.jit, static_argnames=("k",))
def _recommend_jit(
    user_rows: jax.Array,  # (B,) int — rows into user_factors
    user_factors: jax.Array,  # (U, K) device-resident
    item_factors: jax.Array,  # (I, K) device-resident
    exclude_mask: jax.Array,  # (B, I) bool
    k: int,
):
    scores = user_factors[user_rows] @ item_factors.T  # (B, I) — MXU
    return masked_top_k(scores, k, exclude_mask)


@partial(jax.jit, static_argnames=("k",))
def _recommend_jit_nomask(
    user_rows: jax.Array,
    user_factors: jax.Array,
    item_factors: jax.Array,
    k: int,
):
    scores = user_factors[user_rows] @ item_factors.T
    return jax.lax.top_k(scores, k)


# serving kernels opt into full memory_analysis (memory=True): the
# duplicate AOT compile per signature is ~100 ms and lands in warmup —
# the bucket ladder pre-compiles every live shape before traffic
_recommend_jit = _devprof.instrument(
    "als.recommend_masked", _recommend_jit, memory=True
)
_recommend_jit_nomask = _devprof.instrument(
    "als.recommend", _recommend_jit_nomask, memory=True
)


def recommend(
    model: ALSFactors,
    user_indices: np.ndarray,  # (B,) rows into user_factors
    k: int,
    exclude_mask: Optional[np.ndarray] = None,  # (B, I) bool
    item_factors_device: Optional[jax.Array] = None,
    user_factors_device: Optional[jax.Array] = None,
) -> tuple[np.ndarray, np.ndarray]:
    """Top-k items for a batch of users; returns (scores, item_indices).

    The serving hot path is ONE device dispatch: only the (B,) user rows
    (and the mask, when any filter applies) cross host→device per query;
    both factor matrices stay HBM-resident across queries
    (CreateServer-style TPU-resident model state). The unfiltered path
    skips mask allocation entirely."""
    itf = (
        item_factors_device
        if item_factors_device is not None
        else jnp.asarray(model.item_factors)
    )
    uf = (
        user_factors_device
        if user_factors_device is not None
        else jnp.asarray(model.user_factors)
    )
    rows = jnp.asarray(np.asarray(user_indices, dtype=np.int32))
    if exclude_mask is None:
        vals, idx = _recommend_jit_nomask(rows, uf, itf, k)
    else:
        vals, idx = _recommend_jit(rows, uf, itf, jnp.asarray(exclude_mask), k)
    return np.asarray(vals), np.asarray(idx)


# -- staged serving state (ISSUE 11) ----------------------------------------


SERVE_DTYPES = ("f32", "bf16", "int8")


@dataclass(frozen=True)
class ServingFactors:
    """Device-resident serving-side factor state, staged ONCE and reused
    across every call (the donated-resident-state contract: per-query
    traffic is the (B,) row ids and, when filters apply, the packed
    mask words or exclusion row list).

    `items` is row-padded by `ops.recommend_pallas.pad_items`, to the
    widest kernel tile the catalogue's size affords (`item_tile`);
    `n_items` is the live extent (pad rows are masked dead inside the
    kernel and sliced off on the XLA fallback). dtype "int8" holds
    BOTH matrices per-row symmetric-quantized with their scale vectors
    (users (U, 1), items (1, I_p)) — scoring is int8xint8->int32 with the scale outer
    product dequantizing in registers; "bf16" (ISSUE 14, the middle
    ground) halves the factor stream with bf16xbf16->f32 scoring and
    no scale vectors. `item_inv_norm` carries the items' f32-row
    inverse L2 norms so the cosine verbs (`similar_serving`, itemsim's
    on-the-fly cosine) serve off the SAME resident slab — cosine is
    the scaled dot, never a normalized copy in HBM."""

    users: jax.Array  # (U, K) f32 | bf16 | int8
    items: jax.Array  # (I_p, K) f32 | bf16 | int8 — pad rows zero
    user_scale: Optional[jax.Array]  # (U, 1) f32 when int8
    item_scale: Optional[jax.Array]  # (1, I_p) f32 when int8
    n_items: int
    dtype: str  # "f32" | "bf16" | "int8"
    mode: Optional[str]  # resolved pallas mode (None = XLA two-step)
    item_inv_norm: Optional[jax.Array] = None  # (1, I_p) f32 — cosine

    @property
    def n_users(self) -> int:
        return int(self.users.shape[0])

    @property
    def item_tile(self) -> int:
        """The kernel tile this table's padded rows take."""
        from predictionio_tpu.ops.recommend_pallas import pick_item_tile

        return pick_item_tile(int(self.items.shape[0]))

    def device_nbytes(self) -> float:
        total = float(self.users.nbytes + self.items.nbytes)
        if self.user_scale is not None:
            total += float(self.user_scale.nbytes + self.item_scale.nbytes)
        if self.item_inv_norm is not None:
            total += float(self.item_inv_norm.nbytes)
        return total


def stage_serving(
    factors: "ALSFactors",
    serve_dtype: str = "f32",
    mode: str = "auto",
) -> ServingFactors:
    """Stage (and for "int8", quantize) the factor matrices for serving.

    Quantization happens HERE — at model publish / fold-in restage —
    never per query; `serving_publish_rows` keeps a folded tick from
    re-running this on anything but the dirty rows."""
    return _stage_arrays(
        np.asarray(factors.user_factors, np.float32),
        np.asarray(factors.item_factors, np.float32),
        serve_dtype, mode,
    )


def stage_item_serving(
    item_matrix: np.ndarray,
    serve_dtype: str = "f32",
    mode: str = "auto",
) -> ServingFactors:
    """Item-only staging for cosine-only models (itemsim's (I, U)
    column vectors): same ServingFactors contract with an empty user
    side — `similar_serving` is the only verb that makes sense here."""
    itf = np.asarray(item_matrix, np.float32)
    return _stage_arrays(
        np.zeros((0, itf.shape[1] if itf.ndim == 2 else 0), np.float32),
        itf, serve_dtype, mode,
    )


def _stage_arrays(
    uf: np.ndarray, itf: np.ndarray, serve_dtype: str, mode: str
) -> ServingFactors:
    """`_stage_unspanned` as the span `als.serve.stage`: the staging
    until every table is resident, and what the pad rule decided."""
    with _spans.span("als.serve.stage", dtype=serve_dtype) as sp:
        staged = _stage_unspanned(uf, itf, serve_dtype, mode)
        jax.block_until_ready((
            staged.users, staged.items, staged.user_scale,
            staged.item_scale, staged.item_inv_norm,
        ))
        sp.attrs["item_rows_padded"] = int(staged.items.shape[0])
        sp.attrs["item_tile"] = staged.item_tile
        return staged


def _stage_unspanned(
    uf: np.ndarray, itf: np.ndarray, serve_dtype: str, mode: str
) -> ServingFactors:
    from predictionio_tpu.ops import recommend_pallas as _rp

    if serve_dtype not in SERVE_DTYPES:
        raise ValueError(
            f"serve_dtype must be one of {SERVE_DTYPES}, got "
            f"{serve_dtype!r}"
        )
    n_items, k = itf.shape if itf.ndim == 2 else (0, uf.shape[1])
    i_p = _rp.pad_items(n_items)
    # inverse norms from the PRE-quantization f32 rows: the cosine
    # verbs normalize by the true magnitudes, identically across dtypes
    inv = jax.device_put(_rp.inv_norms_np(itf, i_p))
    resolved = _rp.resolve_mode(mode)
    if serve_dtype == "int8":
        uq, us = _rp.quantize_rows_np(uf)
        iq, isc = _rp.quantize_rows_np(itf)
        items = np.zeros((i_p, k), np.int8)
        items[:n_items] = iq
        iscale = np.ones((1, i_p), np.float32)
        iscale[0, :n_items] = isc
        return ServingFactors(
            users=jax.device_put(uq),
            items=jax.device_put(items),
            user_scale=jax.device_put(us[:, None]),
            item_scale=jax.device_put(iscale),
            n_items=n_items,
            dtype="int8",
            mode=resolved,
            item_inv_norm=inv,
        )
    np_dt = np.float32
    items = np.zeros((i_p, k), np_dt)
    items[:n_items] = itf
    users_dev = jax.device_put(uf)
    items_dev = jax.device_put(items)
    if serve_dtype == "bf16":
        users_dev = users_dev.astype(jnp.bfloat16)
        items_dev = items_dev.astype(jnp.bfloat16)
    return ServingFactors(
        users=users_dev,
        items=items_dev,
        user_scale=None,
        item_scale=None,
        n_items=n_items,
        dtype=serve_dtype,
        mode=resolved,
        item_inv_norm=inv,
    )


def _serve_dtype_of(items: jax.Array) -> str:
    dt = str(items.dtype)
    return "int8" if dt == "int8" else ("bf16" if dt == "bfloat16" else "f32")


def _fused_or_xla_topk(
    q, items, qs, isc, mask_bits, excl_rows, n_items, *, k, mode
):
    """One dispatch seam for every serving verb, shared with the
    sharded tier: ops/recommend_pallas.py:fused_or_xla_topk (the fused
    one-pass kernel where a mode resolved, else the XLA two-step with
    IDENTICAL scoring + exclusion semantics — incl. the batch-size-
    stable `q @ items.T` dot spelling its docstring records)."""
    from predictionio_tpu.ops.recommend_pallas import fused_or_xla_topk

    return fused_or_xla_topk(
        q, items, qs, isc, mask_bits, excl_rows, n_items, k=k, mode=mode
    )


@partial(jax.jit, static_argnames=("k", "mode"))
def _serve_recommend_jit(
    rows: jax.Array,  # (B,) int32 — the per-call traffic
    users: jax.Array,
    items: jax.Array,
    user_scale: Optional[jax.Array],
    item_scale: Optional[jax.Array],
    mask_bits: Optional[jax.Array],  # (B, I_p/32) int32 packed words
    excl_rows: Optional[jax.Array],  # (B, E) int32 row list, -1 padded
    n_items: jax.Array,  # () int32 live item count, TRACED — online
    # vocab growth within the pad must not retrace the serving program
    *,
    k: int,
    mode: Optional[str],
):
    """The staged-state serving program: gather the query block from the
    resident user matrix, then either the fused one-pass Pallas kernel
    (mode "tpu"/"interpret") or the XLA two-step fallback — both share
    the int8/bf16 scoring semantics (quantized gather, int32/f32
    accumulate, scale-product dequant), so a mode change never changes
    int8 or bf16 scores; f32 scores can move by the MXU's bf16 operand
    rounding at B = 1 on the TPU (ops/recommend_pallas.py, "On the
    chip")."""
    int8 = items.dtype == jnp.int8
    q = users[rows]
    qs = user_scale[rows] if int8 else None
    isc = item_scale if int8 else None
    return _fused_or_xla_topk(
        q, items, qs, isc, mask_bits, excl_rows, n_items, k=k, mode=mode
    )


@partial(jax.jit, static_argnames=("k", "mode"))
def _serve_similar_jit(
    rows: jax.Array,  # (B,) int32 item rows — the per-call traffic
    items: jax.Array,
    item_scale: Optional[jax.Array],
    item_inv_norm: jax.Array,  # (1, I_p) f32
    mask_bits: Optional[jax.Array],
    excl_rows: Optional[jax.Array],
    n_items: jax.Array,
    *,
    k: int,
    mode: Optional[str],
):
    """Fused cosine `similar` off the SAME resident item slab as
    recommend (ISSUE 14 tentpole part 1): cosine(q, x) =
    (q·x)·(1/|q|)·(1/|x|) — the inverse norms ride the kernel's scale
    inputs, so no normalized factor copy ever exists in HBM. int8
    composes: the effective scales are (dequant scale · inverse norm)
    per side."""
    q = items[rows]
    inv_q = item_inv_norm[0, rows][:, None]  # (B, 1)
    if items.dtype == jnp.int8:
        qs = item_scale[0, rows][:, None] * inv_q
        isc = item_scale * item_inv_norm
    else:
        qs = inv_q
        isc = item_inv_norm
    return _fused_or_xla_topk(
        q, items, qs, isc, mask_bits, excl_rows, n_items, k=k, mode=mode
    )


@partial(jax.jit, static_argnames=("k", "mode"))
def _serve_similar_vecs_jit(
    vecs: jax.Array,  # (B, K) f32 query vectors (basket means)
    items: jax.Array,
    item_scale: Optional[jax.Array],
    item_inv_norm: jax.Array,
    mask_bits: Optional[jax.Array],
    excl_rows: Optional[jax.Array],
    n_items: jax.Array,
    *,
    k: int,
    mode: Optional[str],
):
    """Cosine top-k against ARBITRARY f32 query vectors (the
    similarproduct basket mean) from the staged state: the query side
    quantizes in-jit for int8 slabs (quantize_rows_jnp), norms fold
    into the scale product like every other cosine verb."""
    from predictionio_tpu.ops.recommend_pallas import quantize_rows_jnp

    inv_q = 1.0 / (
        jnp.linalg.norm(vecs, axis=-1, keepdims=True) + 1e-9
    )
    if items.dtype == jnp.int8:
        q, qscale = quantize_rows_jnp(vecs)
        qs = qscale * inv_q
        isc = item_scale * item_inv_norm
    else:
        q = vecs.astype(items.dtype)
        qs = inv_q
        isc = item_inv_norm
    return _fused_or_xla_topk(
        q, items, qs, isc, mask_bits, excl_rows, n_items, k=k, mode=mode
    )


# serving kernels opt into memory analysis (bucket-ladder warmup pays the
# duplicate AOT compile); int8/bf16 signatures roofline against their
# dtype's peak via devprof's dtype-aware table (ISSUE 11 satellite) —
# only the call site knows the resident item matrix IS the MXU dtype
_serve_recommend_jit = _devprof.instrument(
    "als.recommend_serving", _serve_recommend_jit, memory=True,
    dtype_of=lambda args, kwargs: _serve_dtype_of(args[2]),
)
_serve_similar_jit = _devprof.instrument(
    "als.similar_serving", _serve_similar_jit, memory=True,
    dtype_of=lambda args, kwargs: _serve_dtype_of(args[1]),
)
_serve_similar_vecs_jit = _devprof.instrument(
    "als.similar_vecs_serving", _serve_similar_vecs_jit, memory=True,
    dtype_of=lambda args, kwargs: _serve_dtype_of(args[1]),
)


def _exclusion_device_args(
    serving: ServingFactors,
    batch: int,
    exclude_mask: Optional[np.ndarray],
    exclude_rows: Optional[np.ndarray],
    extra_rows: Optional[np.ndarray] = None,
):
    """Host-side exclusion packing shared by the serving verbs: a row
    list (the common small-blacklist case) ships (B, E) int32 at a
    pow2-bucketed width; anything wider — or a dense mask — packs to
    bit words at 1/32 the f32 bytes. `extra_rows` appends one
    always-excluded row per query (similar's exclude_self)."""
    from predictionio_tpu.ops import recommend_pallas as _rp

    i_p = int(serving.items.shape[0])
    if exclude_mask is not None:
        mask = np.asarray(exclude_mask, bool)
        if extra_rows is not None:
            mask = mask.copy()
            mask[np.arange(batch), np.asarray(extra_rows)] = True
        return jnp.asarray(_rp.pack_mask_np(mask, i_p)), None
    if exclude_rows is not None and extra_rows is None:
        # fast path: an already -1-padded (B, E) int32 array (the
        # engines' _exclusion_args builds exactly this) ships as-is —
        # re-listing every cell through Python ints per micro-batch
        # would cost more than the exclusion itself
        ex = np.asarray(exclude_rows, np.int32)
        if ex.shape[1] <= _rp.ROWLIST_MAX:
            return None, (jnp.asarray(ex) if ex.shape[1] else None)
    lists: list[list[int]] = [[] for _ in range(batch)]
    if exclude_rows is not None:
        for b, row in enumerate(exclude_rows):
            lists[b] = [int(x) for x in row if int(x) >= 0]
    if extra_rows is not None:
        for b, r in enumerate(np.asarray(extra_rows)):
            lists[b].append(int(r))
    widest = max((len(r) for r in lists), default=0)
    if widest == 0:
        return None, None
    if widest > _rp.ROWLIST_MAX:
        # too wide for the unrolled compare chain: scatter host-side
        # into packed words instead (still 1/32 the f32 mask bytes)
        mask = np.zeros((batch, i_p), bool)
        for b, row in enumerate(lists):
            hits = np.asarray(row, np.int64)
            hits = hits[(hits >= 0) & (hits < i_p)]
            mask[b, hits] = True
        return jnp.asarray(_rp.pack_mask_np(mask, i_p)), None
    return None, jnp.asarray(_rp.rowlist_np(lists))


def recommend_serving(
    serving: ServingFactors,
    user_indices: np.ndarray,
    k: int,
    exclude_mask: Optional[np.ndarray] = None,  # (B, n_items) bool
    exclude_rows: Optional[np.ndarray] = None,  # (B, E) int, -1 padded
) -> tuple[np.ndarray, np.ndarray]:
    """Top-k items from staged serving state; same (scores, indices)
    contract as `recommend`. ONE device dispatch; only the row ids (and
    the packed exclusion words / row list, when filters apply) cross
    host->device."""
    k = min(int(k), serving.n_items)
    if k <= 0 or serving.n_users == 0:
        b = len(np.asarray(user_indices))
        return (
            np.zeros((b, 0), np.float32), np.zeros((b, 0), np.int64),
        )
    # what crosses host->device before the program can be called is a
    # span of its own (ISSUE 37: on the CPU the conversions are most of
    # what `als.predict.device` holds outside the call, the wait and the
    # copies back; the sharded tier's twin is `sharded.dispatch.put`)
    with _spans.span("als.predict.put"):
        rows = jnp.asarray(np.asarray(user_indices, np.int32))
        bits, ex = _exclusion_device_args(
            serving, int(rows.shape[0]), exclude_mask, exclude_rows
        )
        n_items = jnp.asarray(serving.n_items, jnp.int32)
    vals, idx = _serve_recommend_jit(
        rows, serving.users, serving.items, serving.user_scale,
        serving.item_scale, bits, ex, n_items,
        k=k, mode=serving.mode,
    )
    # the two copies back are a span of their own (ISSUE 37): the
    # profiler's wrapper has blocked on the program already, so this is
    # the device-to-host round trips alone (the sharded tier's twin is
    # `sharded.copy_back`)
    with _spans.span("als.predict.copy_back"):
        return np.asarray(vals), np.asarray(idx)


def similar_serving(
    serving: ServingFactors,
    item_indices: np.ndarray,
    k: int,
    exclude_self: bool = True,
    exclude_mask: Optional[np.ndarray] = None,
    exclude_rows: Optional[np.ndarray] = None,
) -> tuple[np.ndarray, np.ndarray]:
    """Fused cosine top-k for a batch of item rows off the staged
    state — `als.similar` and itemsim's on-the-fly column cosine both
    route here (ISSUE 14). exclude_self rides the row-list fast path
    (one entry per query) unless a dense mask is already in play."""
    k = min(int(k), serving.n_items)
    rows_np = np.asarray(item_indices, np.int32)
    if k <= 0 or serving.n_items == 0:
        return (
            np.zeros((len(rows_np), 0), np.float32),
            np.zeros((len(rows_np), 0), np.int64),
        )
    bits, ex = _exclusion_device_args(
        serving, len(rows_np), exclude_mask, exclude_rows,
        extra_rows=rows_np if exclude_self else None,
    )
    vals, idx = _serve_similar_jit(
        jnp.asarray(rows_np), serving.items, serving.item_scale,
        serving.item_inv_norm, bits, ex,
        jnp.asarray(serving.n_items, jnp.int32),
        k=k, mode=serving.mode,
    )
    return np.asarray(vals), np.asarray(idx)


def similar_vectors_serving(
    serving: ServingFactors,
    vectors: np.ndarray,  # (B, K) f32 query vectors
    k: int,
    exclude_mask: Optional[np.ndarray] = None,
    exclude_rows: Optional[np.ndarray] = None,
) -> tuple[np.ndarray, np.ndarray]:
    """Cosine top-k against arbitrary query vectors (similarproduct's
    basket mean) from the staged state."""
    k = min(int(k), serving.n_items)
    vecs = np.asarray(vectors, np.float32)
    if k <= 0 or serving.n_items == 0:
        return (
            np.zeros((len(vecs), 0), np.float32),
            np.zeros((len(vecs), 0), np.int64),
        )
    bits, ex = _exclusion_device_args(
        serving, len(vecs), exclude_mask, exclude_rows
    )
    vals, idx = _serve_similar_vecs_jit(
        jnp.asarray(vecs), serving.items, serving.item_scale,
        serving.item_inv_norm, bits, ex,
        jnp.asarray(serving.n_items, jnp.int32),
        k=k, mode=serving.mode,
    )
    return np.asarray(vals), np.asarray(idx)


# -- device-side fold-in publish (COW + donation where private) -------------


@jax.jit
def _set_rows_cow(table, rows, values):
    """Row publish OFF a SHARED buffer: .at[].set copies, so readers
    holding the old reference (in-flight pipelined batches) keep a live,
    unchanged buffer — the zero-drop swap contract."""
    return table.at[rows].set(values)


@partial(jax.jit, donate_argnums=(0,))
def _set_rows_donated(table, rows, values):
    """Row publish INTO a donated buffer. ONLY for tables this publish
    privately created (the grown/padded successor no reader has seen):
    XLA aliases the buffer and the publish costs the dirty rows, not a
    matrix copy. Donating a shared buffer here would corrupt concurrent
    readers — callers must uphold the privacy invariant."""
    return table.at[rows].set(values)


@jax.jit
def _set_cols_cow(table, cols, values):
    """COW column write for the (1, I_p) item-scale vector."""
    return table.at[0, cols].set(values)


@partial(jax.jit, donate_argnums=(0,))
def _set_cols_donated(table, cols, values):
    return table.at[0, cols].set(values)


# the publish-path jits are tiny row writes, but they ARE top-level
# dispatch boundaries (every fold-in tick pays them): instrumenting
# keeps the serving-state publish visible in the devprof report
_set_rows_cow = _devprof.instrument("als.publish_rows_cow", _set_rows_cow)
_set_rows_donated = _devprof.instrument(
    "als.publish_rows_donated", _set_rows_donated
)
_set_cols_cow = _devprof.instrument("als.publish_cols_cow", _set_cols_cow)
_set_cols_donated = _devprof.instrument(
    "als.publish_cols_donated", _set_cols_donated
)


def _grow_table(table: jax.Array, n_rows: int, axis: int = 0) -> jax.Array:
    """Zero-pad a factor/scale table to `n_rows` along `axis` (device
    concat — the result is PRIVATE to the caller: safe to donate into)."""
    extra = n_rows - int(table.shape[axis])
    if extra <= 0:
        return table
    shape = list(table.shape)
    shape[axis] = extra
    return jnp.concatenate(
        [table, jnp.zeros(shape, table.dtype)], axis=axis
    )


def serving_publish_rows(
    serving: ServingFactors,
    user_rows: Optional[np.ndarray] = None,
    user_vals: Optional[np.ndarray] = None,  # (Ru, K) f32 solved rows
    item_rows: Optional[np.ndarray] = None,
    item_vals: Optional[np.ndarray] = None,
    n_users: Optional[int] = None,
    n_items: Optional[int] = None,
) -> ServingFactors:
    """Publish a fold-in tick's dirty rows into the staged serving state
    WITHOUT re-staging a factor matrix: quantize only the dirty rows
    (int8 mode) and write them device-side. The first write off a
    SHARED table is copy-on-write (in-flight readers keep a live,
    unchanged buffer — zero-drop swaps); vocab growth zero-pads the
    table first (a private device concat) and the row write into that
    private successor is DONATED, so growth costs the dirty rows plus
    one aliased pad, never a host restage."""
    from predictionio_tpu.ops import recommend_pallas as _rp

    n_users = max(
        serving.n_users, 0 if n_users is None else int(n_users)
    )
    n_items_new = max(
        serving.n_items, 0 if n_items is None else int(n_items)
    )
    users, uscale = serving.users, serving.user_scale
    items, iscale = serving.items, serving.item_scale
    inv = serving.item_inv_norm
    int8 = serving.dtype == "int8"

    if user_rows is not None and len(user_rows) > 0:
        ur = jnp.asarray(np.asarray(user_rows, np.int32))
        uv = np.asarray(user_vals, np.float32)
        grown = n_users > serving.n_users
        if grown:
            users = _grow_table(users, n_users)  # private successor
        set_rows = _set_rows_donated if grown else _set_rows_cow
        if int8:
            q, s = _rp.quantize_rows_np(uv)
            users = set_rows(users, ur, jnp.asarray(q))
            if grown:
                uscale = _grow_table(uscale, n_users)
                uscale = _set_rows_donated(
                    uscale, ur, jnp.asarray(s[:, None])
                )
            else:
                uscale = _set_rows_cow(uscale, ur, jnp.asarray(s[:, None]))
        else:
            users = set_rows(users, ur, jnp.asarray(uv, users.dtype))
    elif n_users > serving.n_users:
        users = _grow_table(users, n_users)
        if int8:
            uscale = _grow_table(uscale, n_users)

    if item_rows is not None and len(item_rows) > 0:
        ir = jnp.asarray(np.asarray(item_rows, np.int32))
        iv = np.asarray(item_vals, np.float32)
        i_p = int(items.shape[0])
        grown = n_items_new > i_p  # growth past the staged pad headroom
        i_p_new = _rp.pad_items(n_items_new)
        if grown:
            items = _grow_table(items, i_p_new)
        set_rows = _set_rows_donated if grown else _set_rows_cow
        set_cols = _set_cols_donated if grown else _set_cols_cow
        if int8:
            q, s = _rp.quantize_rows_np(iv)
            items = set_rows(items, ir, jnp.asarray(q))
            if grown:
                iscale = _grow_table(iscale, i_p_new, axis=1)
            iscale = set_cols(iscale, ir, jnp.asarray(s))
        else:
            items = set_rows(items, ir, jnp.asarray(iv, items.dtype))
        if inv is not None:
            # the cosine verbs' inverse norms track the dirty rows'
            # NEW f32 magnitudes — a fold tick must not serve stale
            # norms under similar while recommend sees fresh factors
            if grown:
                inv = _grow_table(inv, i_p_new, axis=1)
            inv = set_cols(
                inv, ir, jnp.asarray(_rp.inv_norms_np(iv)[0])
            )
    elif n_items_new > int(items.shape[0]):
        items = _grow_table(items, _rp.pad_items(n_items_new))
        if int8:
            iscale = _grow_table(
                iscale, _rp.pad_items(n_items_new), axis=1
            )
        if inv is not None:
            inv = _grow_table(inv, _rp.pad_items(n_items_new), axis=1)

    return ServingFactors(
        users=users, items=items, user_scale=uscale, item_scale=iscale,
        n_items=n_items_new, dtype=serving.dtype, mode=serving.mode,
        item_inv_norm=inv,
    )


@partial(jax.jit, static_argnames=("k",))
def _similar_jit(query_vecs: jax.Array, item_factors: jax.Array, exclude_mask, k: int):
    # cosine similarity on L2-normalized factors
    qn = query_vecs / (jnp.linalg.norm(query_vecs, axis=-1, keepdims=True) + 1e-9)
    fn = item_factors / (jnp.linalg.norm(item_factors, axis=-1, keepdims=True) + 1e-9)
    return masked_top_k(qn @ fn.T, k, exclude_mask)


_similar_jit = _devprof.instrument("als.similar", _similar_jit, memory=True)


def similar_items(
    model: ALSFactors,
    item_indices: np.ndarray,
    k: int,
    exclude_self: bool = True,
) -> tuple[np.ndarray, np.ndarray]:
    """Item-item cosine over factors (similarproduct template's core,
    examples/scala-parallel-similarproduct)."""
    itf = jnp.asarray(model.item_factors)
    q = itf[np.asarray(item_indices)]
    n_items = itf.shape[0]
    mask = np.zeros((len(item_indices), n_items), dtype=bool)
    if exclude_self:
        mask[np.arange(len(item_indices)), np.asarray(item_indices)] = True
    vals, idx = _similar_jit(q, itf, jnp.asarray(mask), k)
    return np.asarray(vals), np.asarray(idx)


def score_pairs(model: ALSFactors, user_idx: np.ndarray, item_idx: np.ndarray) -> np.ndarray:
    """Predicted rating/score for explicit (user, item) pairs — used by eval
    metrics (RMSE) and batch predict."""
    u = model.user_factors[np.asarray(user_idx)]
    i = model.item_factors[np.asarray(item_idx)]
    return np.sum(u * i, axis=-1)


# ---------------------------------------------------------------------------
# Online fold-in (ISSUE 9): single-side incremental solve
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("implicit", "cg_iterations"))
def _fold_in_jit(
    fixed: jax.Array,  # (N, K) — the OPPOSITE side's factors, held fixed
    edge_idx: jax.Array,  # (R, E) int32 — rows into `fixed` (0 on pads)
    edge_val: jax.Array,  # (R, E) — ratings/weights (0 on pads)
    edge_ok: jax.Array,  # (R, E) — 1.0 real edge / 0.0 padding
    lam: jax.Array,  # () f32
    alpha: jax.Array,  # () f32
    *,
    implicit: bool,
    cg_iterations: int,
) -> jax.Array:
    """Solve R dirty rows' k×k regularized normal-equation systems against
    the fixed opposite factor matrix — ONE ALS half-step restricted to the
    dirty rows (the classic fold-in). Identical operator assembly to
    `_half_step_implicit` / `_half_step_explicit`, but over a dense
    (R, E) per-row edge block instead of the global COO list, so a tick's
    worth of new/changed users solves as one tiny batched device program.
    lam/alpha ride as traced scalars: parameter changes don't recompile."""
    n, k = fixed.shape
    y = fixed[edge_idx]  # (R, E, K)
    eye = jnp.eye(k, dtype=jnp.float32)
    if implicit:
        conf = 1.0 + alpha * jnp.abs(edge_val)
        pref = (edge_val > 0).astype(jnp.float32)
        w_b = conf * pref * edge_ok
        w_g = (conf - 1.0) * edge_ok
        gram = f32_gram(fixed)
        b = jnp.einsum("re,rek->rk", w_b, y)
        a = (
            jnp.einsum("re,rek,rel->rkl", w_g, y, y)
            + gram[None, :, :]
            + lam * eye
        )
    else:
        w_b = edge_val * edge_ok
        b = jnp.einsum("re,rek->rk", w_b, y)
        deg = jnp.sum(edge_ok, axis=1)
        reg = lam * jnp.maximum(deg, 1.0)
        a = (
            jnp.einsum("re,rek,rel->rkl", edge_ok, y, y)
            + reg[:, None, None] * eye
        )

    def matvec(v):
        return jnp.einsum("rkl,rl->rk", a, v)

    return batched_cg(matvec, b, jnp.zeros_like(b), cg_iterations)


_fold_in_jit = _devprof.instrument("als.fold_in", _fold_in_jit, memory=True)


def _fold_edge_bucket(n: int) -> int:
    """Pow2 ladder with a floor of 8 for the per-row edge axis — bounds
    distinct compiled fold-in shapes the way serving buckets do."""
    return max(8, 1 << (max(n, 1) - 1).bit_length())


def fold_in_rows(
    fixed: np.ndarray,  # (N, K) opposite-side factors (host or device)
    edges: Sequence[Sequence[tuple[int, float]]],  # per dirty row: (fixed_row, value)
    params: ALSParams,
    fixed_device: Optional[jax.Array] = None,
) -> np.ndarray:
    """Public single-side fold-in solve (ISSUE 9): for each dirty row,
    solve its regularized least-squares system against the FIXED opposite
    factor matrix and return the (R, K) solved factors.

    Row/edge axes are bucketed to a small pow2 ladder so a streaming
    consumer's ticks reuse a handful of compiled programs; pads carry
    edge_ok=0 and are inert in every term (same discipline as the train
    paths). Rows with zero edges solve to exactly zero."""
    from predictionio_tpu.utils.bucket import batch_bucket

    if not edges:
        return np.zeros((0, params.rank), np.float32)
    r_real = len(edges)
    r_pad = batch_bucket(r_real)
    e_pad = _fold_edge_bucket(max(len(e) for e in edges))
    idx = np.zeros((r_pad, e_pad), np.int32)
    val = np.zeros((r_pad, e_pad), np.float32)
    ok = np.zeros((r_pad, e_pad), np.float32)
    for r, row in enumerate(edges):
        for e, (j, v) in enumerate(row):
            idx[r, e] = j
            val[r, e] = v
            ok[r, e] = 1.0
    fx = fixed_device if fixed_device is not None else jnp.asarray(
        np.asarray(fixed, np.float32)
    )
    solved = _fold_in_jit(
        fx, jnp.asarray(idx), jnp.asarray(val), jnp.asarray(ok),
        jnp.float32(params.lambda_), jnp.float32(params.alpha),
        implicit=params.implicit_prefs,
        cg_iterations=params.cg_iterations,
    )
    return np.asarray(solved)[:r_real]


def warm_start_factors(
    parent: ALSFactors,
    user_vocab: BiMap,
    item_vocab: BiMap,
    params: ALSParams,
) -> tuple[np.ndarray, np.ndarray]:
    """Map a parent version's factors onto a NEW training vocabulary —
    the warm start that makes periodic retrains reconverge with the
    stream instead of re-deriving it from noise (ISSUE 9). Rows whose id
    survives copy the parent's factors; brand-new rows get the standard
    scaled gaussian init (ALS is memoryless in factor state, so a warm
    start changes the trajectory, not the fixed point)."""
    rng = np.random.RandomState(params.seed)

    def align(old_vocab: BiMap, new_vocab: BiMap, old: np.ndarray, n: int):
        out = (
            rng.standard_normal((n, params.rank)).astype(np.float32)
            / np.sqrt(params.rank)
        )
        k = min(params.rank, old.shape[1]) if old.size else 0
        for ident, new_row in new_vocab.items():
            old_row = old_vocab.get(ident)
            if old_row is not None and old_row < old.shape[0] and k:
                out[new_row, :k] = old[old_row, :k]
        return out

    uf0 = align(
        parent.user_vocab, user_vocab, parent.user_factors, len(user_vocab)
    )
    itf0 = align(
        parent.item_vocab, item_vocab, parent.item_factors, len(item_vocab)
    )
    return uf0, itf0
