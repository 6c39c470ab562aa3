"""Pallas TPU kernel for the windowed ALS edge pass (per-chunk).

Replaces the one-hot contraction inside ops/windowed.windowed_gram_b's
chunk scan: the XLA path materializes, per chunk, the (CB, B_E, S)
one-hot and the (CB, B_E, K+K²) outer-product payload in HBM (together
~40 GB of write+read traffic per ML-20M edge pass); this kernel builds
both in VMEM and emits only the per-block (S, K) / (S, K²) partial sums
— the same partials the XLA path produces — so the existing block-level
segment-sum combine is unchanged.

The kernel stays INSIDE the scan (one pallas_call per chunk, grid = one
step per block) rather than spanning the whole edge list: a whole-pass
kernel needs the gathered factor rows for every edge materialized at
once (~GBs, plus a relayout at the pallas boundary), which measured
SLOWER than the XLA path at ML-20M; per chunk the gather stays small
and overlaps the kernel through XLA's scheduler.

Everything edge-indexed keeps the 1024-wide edge axis in LANES (factor
rows arrive transposed (K, B_E)): the (K², B_E) outer product is a
sublane concat of full-lane pieces, so VMEM holds no lane-padded narrow
arrays, and both contractions run edge-axis against edge-axis on the
MXU with no in-kernel transposes.

Integration: ops/windowed.windowed_gram_b dispatches here when
`PIO_PALLAS_WINDOWED` allows it (default: on when the default device is
a TPU; `0` forces the XLA path; `interpret` runs this kernel through the
Pallas interpreter on CPU — how tests/test_windowed_pallas.py checks
agreement with the XLA path).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _kernel(yt_ref, wb_ref, wg_ref, local_ref, b_ref, g_ref):
    """One grid step = one edge block.

    b_partial    = (onehot·w_b) @ yᵀ          (S, K)
    gram_partial = (onehot·w_g) @ outer(y)ᵀ   (S, K²)
    """
    yt = yt_ref[0]  # (K, B_E) f32 — gathered fixed-side rows, transposed
    k = yt.shape[0]
    lid = local_ref[0]  # (1, B_E) int32; padding slots carry w_b=w_g=0
    s_rows = b_ref.shape[1]
    rows = jax.lax.broadcasted_iota(jnp.int32, (s_rows, lid.shape[1]), 0)
    onehot = (rows == lid).astype(jnp.float32)  # (S, B_E) — VMEM only

    dot_e = functools.partial(
        jax.lax.dot_general,  # contract both operands on their edge axis
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        # HIGHEST: CG consumes these sums; one bf16 MXU pass loses ~2^-8
        precision=jax.lax.Precision.HIGHEST,
    )
    b_ref[0] = dot_e(onehot * wb_ref[0], yt)
    # outer_t[i*K+j, e] = y[e,i]·y[e,j] — K sublane-stacked (K, B_E) pieces
    outer_t = jnp.concatenate(
        [yt * yt[i : i + 1, :] for i in range(k)], axis=0
    )  # (K², B_E)
    g_ref[0] = dot_e(onehot * wg_ref[0], outer_t)


@functools.partial(jax.jit, static_argnames=("s_rows", "interpret"))
def block_partials(
    y_t: jax.Array,  # (CB, K, B_E) f32 — factors[src] per block, TRANSPOSED
    # so the wide edge axis sits in lanes (a (·, B_E, K) layout would cost
    # a 12.8× lane-pad relayout at the pallas boundary)
    w_b: jax.Array,  # (CB, B_E) f32 — b-vector edge weights (0 on pads)
    w_g: jax.Array,  # (CB, B_E) f32 — gram edge weights (0 on pads)
    local: jax.Array,  # (CB, B_E) int32 — dst % s_rows (arbitrary values
    # outside [0, s_rows) on padding slots never match a one-hot row)
    *,
    s_rows: int = 128,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """One chunk's per-block partial sums → ((CB, S, K), (CB, S, K²)).

    partial_b[c, r]    = Σ_{e in block c: local=r} w_b[e] · y[e]
    partial_gram[c, r] = Σ_{e in block c: local=r} w_g[e] · y[e] ⊗ y[e]

    Callers (windowed_gram_b) segment-sum the block partials into window
    rows exactly as they do for the XLA einsum path.
    """
    from jax.experimental import pallas as pl

    n_blocks, k, b_e = y_t.shape
    # Mosaic requires the last two block dims to divide (8, 128) or equal
    # the array dims — a singleton middle axis makes (1, 1, B_E) legal.
    w_b = w_b.reshape(n_blocks, 1, b_e)
    w_g = w_g.reshape(n_blocks, 1, b_e)
    local = local.reshape(n_blocks, 1, b_e)
    return pl.pallas_call(
        _kernel,
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((1, k, b_e), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 1, b_e), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 1, b_e), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 1, b_e), lambda i: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, s_rows, k), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, s_rows, k * k), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_blocks, s_rows, k), jnp.float32),
            jax.ShapeDtypeStruct((n_blocks, s_rows, k * k), jnp.float32),
        ],
        interpret=interpret,
    )(y_t, w_b, w_g, local)


# device profiling (ISSUE 3): only top-level dispatches record (the train
# loop traces through); cost_analysis of a pallas_call may legitimately
# report 0 flops — the registry then shows invocations/seconds only
from predictionio_tpu.obs import devprof as _devprof  # noqa: E402

block_partials = _devprof.instrument(
    "ops.windowed_block_partials", block_partials
)

