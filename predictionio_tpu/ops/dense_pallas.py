"""Pallas TPU kernel for the dense-W ALS half-step: ONE R read per pass.

The XLA dense path (ops/dense.py) is R-bandwidth-bound: its two
dot_generals each fuse their own weight-tile derivation, so the int8
rating matrix streams from HBM TWICE per half-step (measured ~62% of
the HBM roof at ML-20M; the single-stacked-dot alternative is 2.5×
slower because XLA materializes the concatenated operand — see
dense_row_pass). This kernel loads each R tile into VMEM once, derives
BOTH weight tiles in registers, and issues both MXU dots against the
resident factor slices — halving the dominant HBM term.

Layout: grid (row_tiles, col_tiles) with the column axis innermost; the
two outputs (b (BR, K), corr (BR, K²)) revisit the same block across
the inner axis and accumulate (zeroed at j == 0). The implicit-ALS
weights fold the confidence scale into the dequant:

    w1 = 1[q > 0] + (α/s)·relu(q)        wg = (α/s)·|q|
    (explicit:  w1 = q/s,  wg = 1[q != 0])

`alpha/s` arrives as an SMEM scalar so a traced α never forces a
retrace. int8 storage only — the f32/bf16 modes keep the XLA path.

Gated by PIO_PALLAS_DENSE and DEFAULT-OFF — measured SLOWER than the
XLA two-dot path at ML-20M (see resolve_mode for the arithmetic of the
negative result); kept correct + opt-in for future chip generations.
Interpret mode backs the CPU equivalence tests.
"""

from __future__ import annotations
from predictionio_tpu.utils.env import env_str as _env_str

import functools

import jax
import jax.numpy as jnp

ROW_TILE = 1024
COL_TILE = 1280


def _make_row_kernel(implicit: bool):
    from jax.experimental import pallas as pl

    def kernel(ascale_ref, r_ref, y_ref, z_ref, b_ref, c_ref):
        # f32 derivation: Mosaic vector compare exists ONLY for f32 on
        # this target (int8 and bf16 cmp both fail to lower)
        qf = r_ref[...].astype(jnp.float32)  # (BR, BC)
        a = ascale_ref[0]
        if implicit:
            w1 = (qf > 0).astype(jnp.float32) + a * jnp.maximum(qf, 0.0)
            wg = a * jnp.abs(qf)
        else:
            w1 = a * qf
            wg = (qf != 0).astype(jnp.float32)
        w1 = w1.astype(jnp.bfloat16)
        wg = wg.astype(jnp.bfloat16)
        b = jax.lax.dot_general(
            w1, y_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        c = jax.lax.dot_general(
            wg, z_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

        @pl.when(pl.program_id(1) == 0)
        def _init():
            b_ref[...] = jnp.zeros_like(b_ref)
            c_ref[...] = jnp.zeros_like(c_ref)

        b_ref[...] += b
        c_ref[...] += c

    return kernel


def _make_col_kernel(implicit: bool):
    from jax.experimental import pallas as pl

    def kernel(ascale_ref, r_ref, x_ref, zx_ref, b_ref, c_ref):
        # f32 derivation (see row kernel: only f32 cmp lowers)
        qf = r_ref[...].astype(jnp.float32)  # (BR, BC); rows contract
        a = ascale_ref[0]
        if implicit:
            w1 = (qf > 0).astype(jnp.float32) + a * jnp.maximum(qf, 0.0)
            wg = a * jnp.abs(qf)
        else:
            w1 = a * qf
            wg = (qf != 0).astype(jnp.float32)
        w1 = w1.astype(jnp.bfloat16)
        wg = wg.astype(jnp.bfloat16)
        b = jax.lax.dot_general(
            w1, x_ref[...], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (BC, K)
        c = jax.lax.dot_general(
            wg, zx_ref[...], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (BC, K²)

        @pl.when(pl.program_id(1) == 0)
        def _init():
            b_ref[...] = jnp.zeros_like(b_ref)
            c_ref[...] = jnp.zeros_like(c_ref)

        b_ref[...] += b
        c_ref[...] += c

    return kernel


def _tiles(n: int, t: int) -> int:
    if n % t:
        raise ValueError(f"dim {n} not divisible by tile {t}")
    return n // t


@functools.partial(
    jax.jit,
    static_argnames=("implicit", "interpret", "row_tile", "col_tile"),
)
def fused_row_pass(  # lint: disable=jit-boundary — inner boundary:
    # only invoked inside the instrumented als train jits, where this
    # jit inlines into the trace; instrumenting would record nothing
    r: jax.Array,  # (n_rows_p, n_cols_p) int8
    y: jax.Array,  # (n_cols_p, K) f32
    z: jax.Array,  # (n_cols_p, K²) f32
    ascale: jax.Array,  # (1,) f32 — α/s (implicit) or 1/s (explicit)
    *,
    implicit: bool,
    interpret: bool = False,
    row_tile: int = ROW_TILE,
    col_tile: int = COL_TILE,
) -> tuple[jax.Array, jax.Array]:
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n_rows, n_cols = r.shape
    k = y.shape[1]
    gi, gj = _tiles(n_rows, row_tile), _tiles(n_cols, col_tile)
    y16 = y.astype(jnp.bfloat16)
    z16 = z.astype(jnp.bfloat16)
    return pl.pallas_call(
        _make_row_kernel(implicit),
        grid=(gi, gj),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((row_tile, col_tile), lambda i, j: (i, j)),
            pl.BlockSpec((col_tile, k), lambda i, j: (j, 0)),
            pl.BlockSpec((col_tile, k * k), lambda i, j: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((row_tile, k), lambda i, j: (i, 0)),
            pl.BlockSpec((row_tile, k * k), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_rows, k), jnp.float32),
            jax.ShapeDtypeStruct((n_rows, k * k), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(ascale, r, y16, z16)


@functools.partial(
    jax.jit,
    static_argnames=("implicit", "interpret", "row_tile", "col_tile"),
)
def fused_col_pass(  # lint: disable=jit-boundary — inner boundary:
    # only invoked inside the instrumented als train jits, where this
    # jit inlines into the trace; instrumenting would record nothing
    r: jax.Array,  # (n_rows_p, n_cols_p) int8
    x: jax.Array,  # (n_rows_p, K) f32 — row-side factors
    zx: jax.Array,  # (n_rows_p, K²) f32
    ascale: jax.Array,  # (1,) f32
    *,
    implicit: bool,
    interpret: bool = False,
    row_tile: int = ROW_TILE,
    col_tile: int = COL_TILE,
) -> tuple[jax.Array, jax.Array]:
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n_rows, n_cols = r.shape
    k = x.shape[1]
    gi, gj = _tiles(n_cols, col_tile), _tiles(n_rows, row_tile)
    x16 = x.astype(jnp.bfloat16)
    zx16 = zx.astype(jnp.bfloat16)
    return pl.pallas_call(
        _make_col_kernel(implicit),
        grid=(gi, gj),  # outer: output col tile; inner: row accumulate
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((row_tile, col_tile), lambda i, j: (j, i)),
            pl.BlockSpec((row_tile, k), lambda i, j: (j, 0)),
            pl.BlockSpec((row_tile, k * k), lambda i, j: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((col_tile, k), lambda i, j: (i, 0)),
            pl.BlockSpec((col_tile, k * k), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_cols, k), jnp.float32),
            jax.ShapeDtypeStruct((n_cols, k * k), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(ascale, r, x16, zx16)


def pick_tiles(n_rows_p: int, n_cols_p: int) -> tuple[int, int]:
    """Preferred tile sizes dividing the padded dims (static)."""
    row_tile = next(
        (t for t in (1024, 512, 256) if n_rows_p % t == 0), 0
    )
    col_tile = next(
        (
            t
            for t in (1280, 1024, 1536, 768, 640, 512, 384, 256)
            if n_cols_p % t == 0
        ),
        0,
    )
    return row_tile, col_tile


def resolve_mode(requested: str = "auto"):
    """None (XLA dense path — the DEFAULT), "tpu", or "interpret".

    Default OFF by measurement: at ML-20M the kernel runs 0.70 s per
    train vs the XLA path's 0.60 s. The hypothesis (halving the
    dominant HBM term by reading R once) holds on bytes, but the
    in-kernel weight derivation must run in f32 (Mosaic lowers vector
    compares for f32 only) and its VPU cost on every (1024×1280) tile
    exceeds the saved int8 re-read, which XLA's two-dot form overlaps
    with MXU work anyway. Kept in-tree with interpret-mode equivalence
    tests: PIO_PALLAS_DENSE=1 opts in (e.g. for re-measurement on a
    chip generation with cheaper VPU compares or costlier HBM)."""
    if requested in (None, "off"):
        return None
    if requested == "interpret":
        return "interpret"
    env = _env_str("PIO_PALLAS_DENSE").strip()
    if env == "1":
        from predictionio_tpu.utils.jaxenv import on_tpu

        return "tpu" if on_tpu() else None
    if env == "interpret":
        return "interpret"
    return None

