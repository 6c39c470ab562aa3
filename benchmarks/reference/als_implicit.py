"""Plain reference: implicit-feedback ALS (Hu, Koren, Volinsky 2008) as the
recommendation template configures it — alternating least squares over the
confidence-weighted normal equations, each row's K x K system solved by a
fixed number of conjugate-gradient steps from the row's previous value.

    p_ui = 1[r_ui > 0]        c_ui = 1 + alpha * |r_ui|
    A_u  = Y^T Y + sum_i (c_ui - 1) y_i y_i^T + lambda I
    b_u  = sum_i c_ui p_ui y_i
    x_u  <- cg_iterations steps of CG on A_u x = b_u, from the old x_u

Users first, then items, `iterations` times. Start: factors drawn
normal / sqrt(rank) from `jax.random.split(PRNGKey(seed))` (users' key
first) under the partitionable threefry generator — the start is part of
what the configuration states, since two runs from different starts do not
meet.

Straightforward jax.numpy in float32 with every product at
`Precision.HIGHEST`; no kernels, no padding but its own row blocks, nothing
imported from the program and nothing the program made. The matrix of
observations is held densely as int8 (the interactions are small whole
numbers) and visited `block_rows` rows at a time so that it fits beside
nothing else on one chip.

`operand_bits` is for the CONTROL only: (exponent bits, mantissa bits) of a
narrower float that the operands of every product over R (the weights and
the factor columns) are rounded through first, which is what a
lower-precision run of the same train would do. It is `lax.reduce_precision`
and not a pair of casts: the TPU compiler removes a cast to a narrower type
and back (it may keep excess precision), and did, on the v5e (PR 24: a
control written as casts read 1e-4 where the rounding reads 0.2).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
EDGE_CHUNK = 1 << 20


def initial_factors(seed: int, n_users: int, n_items: int, rank: int):
    ku, ki = jax.random.split(jax.random.PRNGKey(seed))
    with jax.threefry_partitionable(True):
        x = jax.random.normal(ku, (n_users, rank), jnp.float32) / jnp.sqrt(rank)
        y = jax.random.normal(ki, (n_items, rank), jnp.float32) / jnp.sqrt(rank)
    return x, y


@functools.partial(jax.jit, donate_argnums=(0,))
def _set_chunk(r, rows, cols, vals):
    return r.at[rows, cols].set(vals, mode="drop")


def dense_observations(rows, cols, vals, n_rows: int, n_cols: int):
    """R as int8, built a chunk of pairs at a time (one scatter of all the
    pairs would need a lane-padded index operand many times R's size)."""
    vals = np.asarray(vals)
    q = np.round(vals).astype(np.int8)
    if not np.array_equal(q.astype(vals.dtype), vals):
        raise ValueError("the reference holds whole-number interactions only")
    r = jnp.zeros((n_rows, n_cols), jnp.int8)
    n = len(rows)
    for lo in range(0, n, EDGE_CHUNK):
        hi = min(lo + EDGE_CHUNK, n)
        pad = EDGE_CHUNK - (hi - lo)
        # the padding pairs point one past the last row and are dropped
        rr = np.concatenate([rows[lo:hi], np.full(pad, n_rows, np.int32)])
        cc = np.concatenate([cols[lo:hi], np.zeros(pad, np.int32)])
        qq = np.concatenate([q[lo:hi], np.zeros(pad, np.int8)])
        r = _set_chunk(r, jnp.asarray(rr), jnp.asarray(cc), jnp.asarray(qq))
    return r


#: fp8 e4m3's step (3 mantissa bits) at a range nothing here leaves: with 4
#: exponent bits `reduce_precision` flushes what e4m3 keeps as subnormals and
#: overflows at 240, which would test the range of the format, not a
#: lower-precision run (those scale their operands into range)
FP8_E4M3 = (5, 3)
BF16 = (8, 7)


def _round_through(a, bits):
    if bits is None:
        return a
    return jax.lax.reduce_precision(a, exponent_bits=bits[0],
                                    mantissa_bits=bits[1])


def _weights(r_blk, alpha, operand_bits):
    r = r_blk.astype(jnp.float32)
    w_b = jnp.where(r > 0, 1.0 + alpha * jnp.abs(r), 0.0)  # c * p
    w_a = alpha * jnp.abs(r)  # c - 1
    return _round_through(w_b, operand_bits), _round_through(w_a, operand_bits)


def _outer_flat(f):
    n, k = f.shape
    return (f[:, :, None] * f[:, None, :]).reshape(n, k * k)


def _cg(a, b, x, steps: int):
    """`steps` steps of conjugate gradients on every row's system at once.
    a (N, K, K), b and x (N, K). A row that is already solved (a zero
    residual: an empty row starts at zero and stays there) is left alone."""

    def mv(v):
        return jnp.einsum("nij,nj->ni", a, v, precision=HIGHEST)

    r = b - mv(x)
    p = r
    rs = jnp.sum(r * r, axis=-1)
    tiny = jnp.maximum(rs, 1.0) * 1e-12
    for _ in range(steps):
        live = rs > tiny
        ap = mv(p)
        step = jnp.where(live, rs / (jnp.sum(p * ap, axis=-1) + 1e-12), 0.0)
        x = x + step[:, None] * p
        r = r - step[:, None] * ap
        rs_new = jnp.where(live, jnp.sum(r * r, axis=-1), rs)
        beta = jnp.where(live, rs_new / (rs + 1e-12), 0.0)
        p = jnp.where(live[:, None], r + beta[:, None] * p, p)
        rs = rs_new
    return x


def _base(fixed, lam):
    k = fixed.shape[1]
    gram = jnp.einsum("nk,nl->kl", fixed, fixed, precision=HIGHEST)
    return gram + lam * jnp.eye(k, dtype=jnp.float32)


@functools.partial(
    jax.jit, static_argnames=("cg_iterations", "block_rows", "operand_bits")
)
def _solve_rows(r, x, y, *, lam, alpha, cg_iterations, block_rows,
                operand_bits):
    """New row-side factors x given the column side y."""
    n_rows, n_cols = r.shape
    k = y.shape[1]
    base = _base(y, lam)
    yo = _round_through(y, operand_bits)
    zo = _round_through(_outer_flat(y), operand_bits)

    def one(i, x_all):
        r_blk = jax.lax.dynamic_slice_in_dim(r, i * block_rows, block_rows)
        x_blk = jax.lax.dynamic_slice_in_dim(x_all, i * block_rows, block_rows)
        w_b, w_a = _weights(r_blk, alpha, operand_bits)
        b = jnp.matmul(w_b, yo, precision=HIGHEST)
        corr = jnp.matmul(w_a, zo, precision=HIGHEST)
        a = corr.reshape(-1, k, k) + base[None]
        return jax.lax.dynamic_update_slice_in_dim(
            x_all, _cg(a, b, x_blk, cg_iterations), i * block_rows, axis=0
        )

    return jax.lax.fori_loop(0, n_rows // block_rows, one, x)


@functools.partial(
    jax.jit, static_argnames=("cg_iterations", "block_rows", "operand_bits")
)
def _solve_cols(r, x, y, *, lam, alpha, cg_iterations, block_rows,
                operand_bits):
    """New column-side factors y given the row side x."""
    n_rows, n_cols = r.shape
    k = x.shape[1]
    base = _base(x, lam)
    xo = _round_through(x, operand_bits)
    zo = _round_through(_outer_flat(x), operand_bits)

    def one(i, acc):
        r_blk = jax.lax.dynamic_slice_in_dim(r, i * block_rows, block_rows)
        x_blk = jax.lax.dynamic_slice_in_dim(xo, i * block_rows, block_rows)
        z_blk = jax.lax.dynamic_slice_in_dim(zo, i * block_rows, block_rows)
        w_b, w_a = _weights(r_blk, alpha, operand_bits)
        b_acc, c_acc = acc
        b_acc = b_acc + jnp.matmul(w_b.T, x_blk, precision=HIGHEST)
        c_acc = c_acc + jnp.matmul(w_a.T, z_blk, precision=HIGHEST)
        return b_acc, c_acc

    acc0 = (
        jnp.zeros((n_cols, k), jnp.float32),
        jnp.zeros((n_cols, k * k), jnp.float32),
    )
    b, corr = jax.lax.fori_loop(0, n_rows // block_rows, one, acc0)
    a = corr.reshape(-1, k, k) + base[None]
    return _cg(a, b, y, cg_iterations)


def train(rows, cols, vals, n_users: int, n_items: int, *, rank: int,
          iterations: int, lambda_: float, alpha: float, cg_iterations: int,
          seed: int, block_rows: int = 2048, operand_bits=None):
    """(user_factors, item_factors) as float32 numpy arrays."""
    # its own padding: whole row blocks, and columns to the chip's lane
    # width; an empty row or column starts at zero and stays there
    n_rows = -(-n_users // block_rows) * block_rows
    n_cols = -(-n_items // 128) * 128
    r = dense_observations(rows, cols, vals, n_rows, n_cols)
    x0, y0 = initial_factors(seed, n_users, n_items, rank)
    x = jnp.zeros((n_rows, rank), jnp.float32).at[:n_users].set(x0)
    y = jnp.zeros((n_cols, rank), jnp.float32).at[:n_items].set(y0)
    kw = dict(
        lam=lambda_, alpha=alpha, cg_iterations=cg_iterations,
        block_rows=block_rows, operand_bits=operand_bits,
    )
    for _ in range(iterations):
        x = _solve_rows(r, x, y, **kw)
        y = _solve_cols(r, x, y, **kw)
    out = np.asarray(x)[:n_users], np.asarray(y)[:n_items]
    del r
    return out
