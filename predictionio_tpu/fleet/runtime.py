"""Sharded serving runtime: TPU-resident, row-sharded factor state
(ISSUE 10 tentpole part 2; ISSUE 14 brings it to dtype/kernel parity
with the single-device tier).

A single-chip serving tier caps the catalog at one HBM's worth of
factor rows. `ShardedRuntime` keeps BOTH factor matrices row-sharded
over a 1-D device mesh (parallel/mesh.py:serving_mesh) and lowers the
serving verbs as sharded executables, so one model serves a catalog
larger than any single chip can load:

- **recommend**: each shard assembles the query block from the rows it
  owns (masked gather + psum — the all-reduce half of the classic
  gather), scores against ITS item slab, takes a LOCAL top-k, and an
  all-gather + second top-k merges the per-shard candidates into the
  global answer. Score traffic never leaves the shard; only (B, k)
  candidates ride the ICI.
- **similar** / **similar_vectors**: the same shape over cosine scores
  — computed as the scaled dot (inverse norms ride the kernel's scale
  inputs, models/als.py discipline), so the ONE resident slab serves
  both verbs with no normalized copy.
- **fold_in**: the single-side normal-equation solve against the FIXED
  opposite matrix — each shard contributes the partial Gram/b terms of
  the edges it owns (dequantized in registers when the slab is
  int8/bf16), one psum assembles the K×K systems, every shard solves
  them redundantly (they are tiny).

ISSUE 14 additions:

- **serve_dtype** ("f32" | "bf16" | "int8"): int8 stages per-row
  symmetric-quantized slabs + scale vectors (~1/3 the resident HBM of
  f32 once scales and inverse norms ride along); bf16 halves it. The
  local score pass matches the single-device semantics exactly —
  int8×int8→int32 with scale-product dequant — on both the fused
  kernel and the XLA fallback.
- **fused local pass for every verb**: with a resolved serve_mode the
  shard-local score+select runs ops/recommend_pallas.py's one-pass
  kernel (per-shard live counts ride its traced SMEM scalar; item rows
  pre-pad by `pad_items`, every slab to a multiple of the widest kernel
  tile the catalogue's size affords).
- **bit-packed exclusion masks**: the (B, I) bool mask input is gone —
  a dense exclusion ships as (B, I_p/32) packed words column-sharded
  over the mesh (1/32 the f32-equivalent bytes), expanded in registers
  by the kernel or unpacked in-jit by the XLA fallback. A row list of
  at most ROWLIST_MAX ids a query (a blacklist) ships as the ids
  themselves, replicated, and each shard renumbers it into its own
  slab's rows (ISSUE 29): `recommend` picks the encoding by shape.
- **donated dirty-row publish** (direction-1 item (c)): `update_*_rows`
  re-quantizes ONLY the dirty rows and, once in-flight readers drain
  (a short writer-priority window on the reader lease), DONATES the
  resident slab into the row write — the publish costs the dirty rows,
  not a slab copy and never a host restage. Readers that cannot drain
  in time fall back to the copy-on-write scatter (zero-drop either
  way).

Padding rows are exactly zero and masked out of every top-k by the
live-count/pad discipline the train paths use. This module imports jax
at module level — reach it via ``predictionio_tpu.fleet``'s lazy
attribute, never from a data-plane import path.
"""

from __future__ import annotations

import contextlib
import logging
import threading
from functools import partial
from typing import Any, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from predictionio_tpu.obs import devprof as _devprof
from predictionio_tpu.obs import spans as _spans
from predictionio_tpu.ops.segment import batched_cg, f32_gram
from predictionio_tpu.ops.topk import NEG_INF
from predictionio_tpu.parallel.mesh import (
    MODEL_AXIS,
    pad_rows_to_shards,
    serving_mesh,
    shard_map,
    shard_rows,
)

log = logging.getLogger(__name__)

#: how long a donated publish waits for in-flight readers to drain
#: before falling back to the copy-on-write scatter
_DONATE_DRAIN_S = 2.0


class OversizedModelError(RuntimeError):
    """The factor state does not fit the given per-device HBM budget."""


def factor_state_bytes(
    n_users: int, n_items: int, rank: int, dtype_bytes: int = 4
) -> int:
    """Resident bytes of the full (unsharded) factor state — what a
    single-device runtime must fit in one HBM."""
    return (n_users + n_items) * rank * dtype_bytes


#: bytes of one stored factor cell, by serve dtype
SERVE_DTYPE_BYTES = {"f32": 4, "bf16": 2, "int8": 1}


def check_single_device_budget(
    n_users: int, n_items: int, rank: int, budget_bytes: float,
    serve_dtype: str = "f32",
) -> None:
    """Raise when a SINGLE-device runtime cannot hold this factor
    state — the gate the sharded tier exists to pass
    (`ResidentServing` runs it before it stages one chip)."""
    need = factor_state_bytes(
        n_users, n_items, rank, SERVE_DTYPE_BYTES[serve_dtype]
    )
    if need > budget_bytes:
        raise OversizedModelError(
            f"factor state needs {need / 1e9:.2f} GB resident but the "
            f"single-device budget is {budget_bytes / 1e9:.2f} GB — "
            "serve it sharded (fleet.ShardedRuntime; the engine's "
            "`shard_serving: true`)"
        )


# ---------------------------------------------------------------------------
# sharded executables
# ---------------------------------------------------------------------------


def _owned_rows(rows: jax.Array, table: jax.Array, n_local: int):
    """Shard-local gather of `table[rows]` contributions: rows this
    shard owns yield their slab row, others yield zero — a psum over
    the shard axis completes the distributed gather. int8/bf16 tables
    contribute as exact f32 (small integers / bf16 values are exact in
    f32, and one shard owns each row, so the psum reconstructs the
    stored row bit-for-bit)."""
    idx = jax.lax.axis_index(MODEL_AXIS)
    loc = rows - idx * n_local
    own = (loc >= 0) & (loc < n_local)
    safe = jnp.clip(loc, 0, n_local - 1)
    vals = table[safe].astype(jnp.float32)
    return jnp.where(own[..., None], vals, 0.0)


def _owned_vec(rows: jax.Array, vec: jax.Array, n_local: int):
    """Owned gather of a (1, i_local) per-row vector (scales, inverse
    norms) at global `rows` → (B, 1) after the caller's psum."""
    idx = jax.lax.axis_index(MODEL_AXIS)
    loc = rows - idx * n_local
    own = (loc >= 0) & (loc < n_local)
    safe = jnp.clip(loc, 0, n_local - 1)
    return jnp.where(own, vec[0, safe], 0.0)[:, None]


def _merge_topk(v: jax.Array, ix: jax.Array, k: int):
    """Local (B, k_l) candidates → global (B, k) top-k: all-gather the
    per-shard candidates along the score axis, then one more top_k."""
    vs = jax.lax.all_gather(v, MODEL_AXIS, axis=1, tiled=True)
    ixs = jax.lax.all_gather(ix, MODEL_AXIS, axis=1, tiled=True)
    vv, sel = jax.lax.top_k(vs, k)
    return vv, jnp.take_along_axis(ixs, sel, axis=1)


def _sharded_call(mesh, local, *, required, optional):
    """ONE shard_map assembler for every serving verb's optional-input
    plumbing: `required`/`optional` are [(array_or_None, spec), ...];
    absent optionals are excluded from the traced inputs (shard_map
    cannot spec None leaves) and re-inflated as None positionals onto
    `local`, whose signature is required-args-first then the optionals
    in declaration order."""
    args = [a for a, _ in required]
    in_specs = [s for _, s in required]
    present = []
    for a, spec in optional:
        present.append(a is not None)
        if a is not None:
            args.append(a)
            in_specs.append(spec)
    n_req = len(required)

    def fn(*xs):
        it = iter(xs[n_req:])
        filled = [next(it) if p else None for p in present]
        return local(*xs[:n_req], *filled)

    return shard_map(
        fn, mesh=mesh, in_specs=tuple(in_specs), out_specs=(P(), P()),
        check=False,
    )(*args)


def _local_score_topk(
    q, itf_l, qs, isc_l, mask_bits_l, excl_local, live_l, *, k_l, mode
):
    """The shard-local score+mask+select every verb shares — the SAME
    seam the single-device tier serves through
    (ops/recommend_pallas.py:fused_or_xla_topk): the fused one-pass
    kernel when a mode resolved (the per-shard live count rides the
    traced SMEM scalar), else the XLA two-step with identical semantics
    (including the batch-size-stable dot spelling its docstring
    records). The exclusion arrives in ONE of its two encodings, never
    both: `mask_bits_l`, this shard's columns of the packed words, or
    `excl_local`, a (B, E) list of SHARD-LOCAL rows in which -1 (the
    pad, and every row another shard owns) is inert; both apply in
    registers."""
    from predictionio_tpu.ops.recommend_pallas import fused_or_xla_topk

    return fused_or_xla_topk(
        q, itf_l, qs, isc_l, mask_bits_l, excl_local, live_l,
        k=k_l, mode=mode,
    )


@partial(
    jax.jit, static_argnames=("k", "n_items", "mesh", "mode")
)
def _sharded_recommend(
    rows: jax.Array,  # (B,) int32, replicated
    uf: jax.Array,  # (U_p, K) row-sharded over mp — f32 | bf16 | int8
    itf: jax.Array,  # (I_p, K) row-sharded over mp
    uscale: Optional[jax.Array],  # (U_p, 1) f32 row-sharded (int8)
    iscale: Optional[jax.Array],  # (1, I_p) f32 col-sharded (int8)
    mask_bits: Optional[jax.Array],  # (B, I_p/32) int32 col-sharded
    excl_rows: Optional[jax.Array] = None,  # (B, E) int32 replicated:
    # GLOBAL item rows, -1 padded — the other encoding of the same
    # exclusion set, never beside mask_bits
    *,
    k: int,
    n_items: int,
    mesh: jax.sharding.Mesh,
    mode: Optional[str] = None,
):
    """Sharded recommend: the shard-local score+select is the SAME
    verb-agnostic fused pass as the single-device path (ISSUE 14),
    amortized by the local-top-k + all-gather merge — each shard never
    materializes even its local (B, i_local) score slab. A row list
    stays a row list into each shard's kernel (ISSUE 29): a shard
    renumbers the global rows into its own slab's and blanks the rest."""
    n_shards = int(mesh.shape[MODEL_AXIS])
    u_local = uf.shape[0] // n_shards
    i_local = itf.shape[0] // n_shards
    k_l = min(k, i_local)
    int8 = uf.dtype == jnp.int8

    def local(rows_l, uf_l, itf_l, uscale_l, iscale_l, mask_l, excl):
        idx = jax.lax.axis_index(MODEL_AXIS)
        qf = jax.lax.psum(
            _owned_rows(rows_l, uf_l, u_local), MODEL_AXIS
        )  # (B, K) f32 — every shard now holds the full query block
        if int8:
            # the stored per-row quantization carries over exactly:
            # values are the resident int8 rows, scale their vector
            q = qf.astype(jnp.int8)
            qs = jax.lax.psum(
                _owned_vec(
                    rows_l, jnp.swapaxes(uscale_l, 0, 1), u_local
                ),
                MODEL_AXIS,
            )
            isc_l_ = iscale_l
        else:
            q = qf.astype(itf_l.dtype)
            qs = isc_l_ = None
        # per-shard live column count: global vocab clipped to my slab
        live_l = jnp.clip(n_items - idx * i_local, 0, i_local)
        excl_local = None
        if excl is not None:
            # global rows -> this slab's: the list's own pad (-1 stays
            # negative) and every row another shard owns become -1, which
            # matches no column in the kernel's compare chain or the XLA
            # scatter
            loc = excl - idx * i_local
            excl_local = jnp.where((loc >= 0) & (loc < i_local), loc, -1)
        v, ix = _local_score_topk(
            q, itf_l, qs, isc_l_, mask_l, excl_local, live_l,
            k_l=k_l, mode=mode,
        )
        return _merge_topk(v, ix + idx * i_local, k)

    sh = P(MODEL_AXIS, None)
    col_sh = P(None, MODEL_AXIS)
    return _sharded_call(
        mesh, local,
        required=[(rows, P()), (uf, sh), (itf, sh)],
        optional=[
            (uscale, sh), (iscale, col_sh), (mask_bits, col_sh),
            (excl_rows, P()),
        ],
    )


@partial(
    jax.jit,
    static_argnames=("k", "n_items", "mesh", "exclude_self", "mode"),
)
def _sharded_similar(
    rows: jax.Array,  # (B,) int32 item rows, replicated
    itf: jax.Array,  # (I_p, K) row-sharded
    iscale: Optional[jax.Array],  # (1, I_p) f32 col-sharded (int8)
    iinv: jax.Array,  # (1, I_p) f32 col-sharded inverse norms
    mask_bits: Optional[jax.Array],
    *,
    k: int,
    n_items: int,
    mesh: jax.sharding.Mesh,
    exclude_self: bool,
    mode: Optional[str] = None,
):
    """Sharded cosine similar off the SAME resident slab as recommend:
    cosine = (q·x)·(1/|q|)·(1/|x|), the inverse norms riding the
    fused kernel's scale inputs. exclude_self translates the query's
    GLOBAL row ids into shard-local ids and rides the kernel's
    row-list input — entries outside the shard never match."""
    n_shards = int(mesh.shape[MODEL_AXIS])
    i_local = itf.shape[0] // n_shards
    k_l = min(k, i_local)
    int8 = itf.dtype == jnp.int8

    def local(rows_l, itf_l, iinv_l, iscale_l, mask_l):
        idx = jax.lax.axis_index(MODEL_AXIS)
        qf = jax.lax.psum(
            _owned_rows(rows_l, itf_l, i_local), MODEL_AXIS
        )
        inv_q = jax.lax.psum(
            _owned_vec(rows_l, iinv_l, i_local), MODEL_AXIS
        )  # (B, 1) — the query rows' staged inverse norms
        if int8:
            q = qf.astype(jnp.int8)
            qscale = jax.lax.psum(
                _owned_vec(rows_l, iscale_l, i_local), MODEL_AXIS
            )
            qs = qscale * inv_q
            isc_l_ = iscale_l * iinv_l
        else:
            q = qf.astype(itf_l.dtype)
            qs = inv_q
            isc_l_ = iinv_l
        live_l = jnp.clip(n_items - idx * i_local, 0, i_local)
        excl_local = (
            (rows_l - idx * i_local)[:, None] if exclude_self else None
        )
        v, ix = _local_score_topk(
            q, itf_l, qs, isc_l_, mask_l, excl_local, live_l,
            k_l=k_l, mode=mode,
        )
        return _merge_topk(v, ix + idx * i_local, k)

    sh = P(MODEL_AXIS, None)
    col_sh = P(None, MODEL_AXIS)
    return _sharded_call(
        mesh, local,
        required=[(rows, P()), (itf, sh), (iinv, col_sh)],
        optional=[(iscale, col_sh), (mask_bits, col_sh)],
    )


@partial(
    jax.jit, static_argnames=("k", "n_items", "mesh", "mode")
)
def _sharded_similar_vecs(
    vecs: jax.Array,  # (B, K) f32 query vectors, replicated
    itf: jax.Array,  # (I_p, K) row-sharded
    iscale: Optional[jax.Array],
    iinv: jax.Array,
    mask_bits: Optional[jax.Array],
    *,
    k: int,
    n_items: int,
    mesh: jax.sharding.Mesh,
    mode: Optional[str] = None,
):
    """Cosine top-k against ARBITRARY query vectors (the
    similarproduct/itemsim basket query) from the sharded state. The
    query side quantizes in-jit for int8 slabs — replicated compute,
    so the answer is device-count invariant."""
    from predictionio_tpu.ops.recommend_pallas import quantize_rows_jnp

    n_shards = int(mesh.shape[MODEL_AXIS])
    i_local = itf.shape[0] // n_shards
    k_l = min(k, i_local)
    int8 = itf.dtype == jnp.int8

    def local(vecs_l, itf_l, iinv_l, iscale_l, mask_l):
        idx = jax.lax.axis_index(MODEL_AXIS)
        inv_q = 1.0 / (
            jnp.linalg.norm(vecs_l, axis=-1, keepdims=True) + 1e-9
        )
        if int8:
            q, qscale = quantize_rows_jnp(vecs_l)
            qs = qscale * inv_q
            isc_l_ = iscale_l * iinv_l
        else:
            q = vecs_l.astype(itf_l.dtype)
            qs = inv_q
            isc_l_ = iinv_l
        live_l = jnp.clip(n_items - idx * i_local, 0, i_local)
        v, ix = _local_score_topk(
            q, itf_l, qs, isc_l_, mask_l, None, live_l,
            k_l=k_l, mode=mode,
        )
        return _merge_topk(v, ix + idx * i_local, k)

    sh = P(MODEL_AXIS, None)
    col_sh = P(None, MODEL_AXIS)
    return _sharded_call(
        mesh, local,
        required=[(vecs, P()), (itf, sh), (iinv, col_sh)],
        optional=[(iscale, col_sh), (mask_bits, col_sh)],
    )


@partial(
    jax.jit,
    static_argnames=("implicit", "cg_iterations", "mesh", "scale_cols"),
)
def _sharded_fold_in(
    fixed: jax.Array,  # (N_p, K) row-sharded — the FIXED opposite side
    fixed_scale: Optional[jax.Array],  # dequant scales (int8 slabs)
    edge_idx: jax.Array,  # (R, E) int32 rows into `fixed` (replicated)
    edge_val: jax.Array,  # (R, E)
    edge_ok: jax.Array,  # (R, E) 1.0 real / 0.0 pad
    lam: jax.Array,  # () f32
    alpha: jax.Array,  # () f32
    *,
    implicit: bool,
    cg_iterations: int,
    mesh: jax.sharding.Mesh,
    scale_cols: bool = False,  # scale layout: (1, N_p) cols vs (N_p, 1)
):
    """Sharded single-side fold-in solve: identical operator assembly to
    models/als.py:_fold_in_jit, with the edge gather distributed — each
    shard contributes the terms of the fixed rows it owns and ONE psum
    assembles the (R, K, K) systems everywhere. Quantized slabs
    dequantize in registers at the gather (the solve itself is f32)."""
    n_shards = int(mesh.shape[MODEL_AXIS])
    n_local = fixed.shape[0] // n_shards
    k = fixed.shape[1]

    def local(fixed_l, fixed_scale_l, edge_idx, edge_val, edge_ok):
        idx = jax.lax.axis_index(MODEL_AXIS)
        loc = edge_idx - idx * n_local
        own = (
            ((loc >= 0) & (loc < n_local)).astype(jnp.float32) * edge_ok
        )
        safe = jnp.clip(loc, 0, n_local - 1)
        fl = fixed_l.astype(jnp.float32)
        if fixed_scale_l is not None:
            row_scale = (
                jnp.swapaxes(fixed_scale_l, 0, 1)
                if scale_cols else fixed_scale_l
            )  # (n_local, 1) either way
            fl = fl * row_scale
        y = fl[safe] * own[..., None]  # (R, E, K) — owner-masked
        eye = jnp.eye(k, dtype=jnp.float32)
        if implicit:
            conf = 1.0 + alpha * jnp.abs(edge_val)
            pref = (edge_val > 0).astype(jnp.float32)
            w_b = conf * pref * own
            w_g = (conf - 1.0) * own
            gram = jax.lax.psum(f32_gram(fl), MODEL_AXIS)
            b = jax.lax.psum(
                jnp.einsum("re,rek->rk", w_b, y), MODEL_AXIS
            )
            a = (
                jax.lax.psum(
                    jnp.einsum("re,rek,rel->rkl", w_g, y, y), MODEL_AXIS
                )
                + gram[None, :, :]
                + lam * eye
            )
        else:
            b = jax.lax.psum(
                jnp.einsum("re,rek->rk", edge_val * own, y), MODEL_AXIS
            )
            deg = jnp.sum(edge_ok, axis=1)  # edge_ok is replicated
            reg = lam * jnp.maximum(deg, 1.0)
            a = (
                jax.lax.psum(
                    jnp.einsum("re,rek,rel->rkl", own, y, y), MODEL_AXIS
                )
                + reg[:, None, None] * eye
            )

        def matvec(v):
            return jnp.einsum("rkl,rl->rk", a, v)

        return batched_cg(matvec, b, jnp.zeros_like(b), cg_iterations)

    sh = P(MODEL_AXIS, None)
    if fixed_scale is not None:
        scale_spec = P(None, MODEL_AXIS) if scale_cols else sh
        return shard_map(
            local, mesh=mesh,
            in_specs=(sh, scale_spec, P(), P(), P()),
            out_specs=P(), check=False,
        )(fixed, fixed_scale, edge_idx, edge_val, edge_ok)
    return shard_map(
        lambda f, ei, ev, eo: local(f, None, ei, ev, eo),
        mesh=mesh,
        in_specs=(sh, P(), P(), P()),
        out_specs=P(), check=False,
    )(fixed, edge_idx, edge_val, edge_ok)


def _make_scatter_rows(donate: bool):
    def scatter(table, rows, values, *, mesh):
        out = table.at[rows].set(values.astype(table.dtype))
        return jax.lax.with_sharding_constraint(
            out, NamedSharding(mesh, P(MODEL_AXIS, None))
        )

    return (
        jax.jit(scatter, static_argnames=("mesh",), donate_argnums=(0,))
        if donate
        else jax.jit(scatter, static_argnames=("mesh",))
    )


def _make_scatter_cols(donate: bool):
    def scatter(vec, cols, values, *, mesh):
        out = vec.at[0, cols].set(values.astype(vec.dtype))
        return jax.lax.with_sharding_constraint(
            out, NamedSharding(mesh, P(None, MODEL_AXIS))
        )

    return (
        jax.jit(scatter, static_argnames=("mesh",), donate_argnums=(0,))
        if donate
        else jax.jit(scatter, static_argnames=("mesh",))
    )


#: COW row update: preserves sharding AND in-flight readers — a reader
#: that captured the old table reference keeps a live buffer (the
#: zero-drop fallback when the donated path cannot drain readers)
_scatter_rows = _make_scatter_rows(donate=False)
#: donated row update (ISSUE 14, direction-1 item (c)): aliases the
#: resident slab into the row write — ONLY safe once `_publish` has
#: drained every in-flight reader lease; the publish then costs the
#: dirty rows, not a slab copy
_scatter_rows_donated = _make_scatter_rows(donate=True)
_scatter_cols = _make_scatter_cols(donate=False)
_scatter_cols_donated = _make_scatter_cols(donate=True)


# serving executables opt into memory analysis like the dense serving
# kernels: the per-signature AOT compile lands in warmup, and the
# temp/output bytes feed the tenant cache's transient accounting.
# dtype_of: the resident item slab's dtype IS the MXU dtype (ISSUE 14)
def _fleet_dtype_of(ix: int):
    def pick(args, kwargs):
        dt = str(getattr(args[ix], "dtype", ""))
        return "int8" if dt == "int8" else (
            "bf16" if dt == "bfloat16" else "f32"
        )

    return pick


_scatter_rows = _devprof.instrument("fleet.scatter_rows", _scatter_rows)
_scatter_rows_donated = _devprof.instrument(
    "fleet.scatter_rows_donated", _scatter_rows_donated
)
_scatter_cols = _devprof.instrument("fleet.scatter_cols", _scatter_cols)
_scatter_cols_donated = _devprof.instrument(
    "fleet.scatter_cols_donated", _scatter_cols_donated
)
_sharded_recommend = _devprof.instrument(
    "fleet.recommend_sharded", _sharded_recommend, memory=True,
    dtype_of=_fleet_dtype_of(2),
)
_sharded_similar = _devprof.instrument(
    "fleet.similar_sharded", _sharded_similar, memory=True,
    dtype_of=_fleet_dtype_of(1),
)
_sharded_similar_vecs = _devprof.instrument(
    "fleet.similar_vecs_sharded", _sharded_similar_vecs, memory=True,
    dtype_of=_fleet_dtype_of(1),
)
# no dtype_of on fold_in: its slab may STORE int8/bf16 but the solve
# dequantizes at the gather and runs entirely in f32 — declaring the
# storage dtype would roofline f32 FLOPs against the int8 peak (dtype
# is compute, never inferred from storage; the PR-11 discipline)
_sharded_fold_in = _devprof.instrument(
    "fleet.fold_in_sharded", _sharded_fold_in, memory=True,
)


#: XLA's CPU collectives run every per-device program on one shared
#: inter-op pool and rendezvous ALL participants before any may finish.
#: Two multi-device executables in flight at once can split the pool's
#: threads across their rendezvous sets on small hosts and starve both
#: forever (observed: concurrent recommend() readers under the 8-way
#: virtual test mesh on 1-2 vCPUs wedge in AllReduce with every thread
#: asleep). Collective dispatch on the cpu platform therefore
#: serializes through one process-wide lock — held only around the
#: launch+block, never while waiting on reader leases, so it is always
#: the innermost lock. Real accelerator streams don't share a host
#: thread pool and skip the lock entirely.
_CPU_COLLECTIVE_LOCK = threading.Lock()


def _collective_guard(mesh):
    devs = mesh.devices
    if devs.size > 1 and devs.flat[0].platform == "cpu":
        return _CPU_COLLECTIVE_LOCK
    return contextlib.nullcontext()


# ---------------------------------------------------------------------------
# the runtime
# ---------------------------------------------------------------------------


class _ShardState(NamedTuple):
    """ONE immutable snapshot of the resident sharded arrays. Readers
    take the whole tuple in one atomic attribute read inside their
    lease and publishes swap it in one assignment — a quantized (int8)
    publish can therefore never be observed with new rows but old
    scales/inverse norms (the torn-pair hazard the per-attribute
    layout had on the COW fallback path)."""

    uf: jax.Array  # (U_p, K) f32 | bf16 | int8, row-sharded
    itf: jax.Array  # (I_p, K), row-sharded
    uscale: Optional[jax.Array]  # (U_p, 1) f32 (int8 only)
    iscale: Optional[jax.Array]  # (1, I_p) f32 (int8 only)
    iinv: jax.Array  # (1, I_p) f32 inverse norms


class ShardedRuntime:
    """Row-sharded, device-resident ALS factor state + the sharded
    serving verbs. Swapped atomically like any other runtime: the query
    server's runtime-swap lock and the tenant model cache treat it as
    opaque model state (tenancy/cache.py's device-bytes walk counts
    only the per-device addressable shard)."""

    SERVE_DTYPES = ("f32", "bf16", "int8")

    def __init__(
        self,
        user_factors: np.ndarray,  # (U, K) f32
        item_factors: np.ndarray,  # (I, K) f32
        user_vocab: Optional[Any] = None,
        item_vocab: Optional[Any] = None,
        params: Optional[Any] = None,
        mesh: Optional[jax.sharding.Mesh] = None,
        device_budget_bytes: Optional[float] = None,
        serve_mode: str = "auto",
        serve_dtype: str = "f32",
    ):
        from predictionio_tpu.ops import recommend_pallas as _rp

        if mesh is None:
            mesh = serving_mesh()
        if MODEL_AXIS not in mesh.shape or len(mesh.shape) != 1:
            raise ValueError(
                "ShardedRuntime needs a 1-D serving mesh "
                f"(parallel.mesh.serving_mesh); got axes {dict(mesh.shape)}"
            )
        if serve_dtype not in self.SERVE_DTYPES:
            raise ValueError(
                f"serve_dtype must be one of {self.SERVE_DTYPES}, got "
                f"{serve_dtype!r}"
            )
        self.mesh = mesh
        self.n_shards = int(mesh.shape[MODEL_AXIS])
        # fused local score+select (ISSUE 11/14): the sharded twin of
        # the one-pass kernel — resolved once here so every serving
        # call traces against a fixed mode
        self.serve_mode = _rp.resolve_mode(serve_mode)
        self.serve_dtype = serve_dtype
        uf = np.asarray(user_factors, np.float32)
        itf = np.asarray(item_factors, np.float32)
        # item rows pad so every shard's slab is tile-divisible
        # (ops/recommend_pallas.pad_items owns the rule, both tiers)
        i_p = _rp.pad_items(
            itf.shape[0], self.n_shards, fused=self.serve_mode is not None
        )
        self.n_users, self.rank = uf.shape
        self.n_items = int(itf.shape[0])
        if device_budget_bytes is not None:
            per_shard = self._staged_bytes_estimate(uf, itf) / self.n_shards
            if per_shard > device_budget_bytes:
                raise OversizedModelError(
                    f"factor state needs {per_shard / 1e9:.2f} GB per "
                    f"shard over {self.n_shards} shard(s) but the "
                    f"per-device budget is "
                    f"{device_budget_bytes / 1e9:.2f} GB"
                )
        self.user_vocab = user_vocab
        self.item_vocab = item_vocab
        self.params = params
        self._lock = threading.Lock()
        # reader-lease state for the donated publish (ISSUE 14): verbs
        # hold a lease while their arrays are in flight; update_*_rows
        # briefly gates new leases, drains the in-flight ones, and
        # donates — or falls back to COW if the drain times out
        self._readers = 0  # guarded-by: _reader_cv
        self._writer_waiting = False  # guarded-by: _reader_cv
        self._poisoned = False  # set by a failed DONATED publish
        self._reader_cv = threading.Condition()
        # ONE staging each: the sharded arrays stay HBM-resident across
        # queries, folds, and swaps (CreateServer-style resident state);
        # they live in ONE immutable _ShardState tuple that readers
        # snapshot atomically and publishes swap atomically
        # staging is spans (ISSUE 27): what the host prepares (inverse
        # norms, int8 quantization), then the sharded puts — the item
        # pad rides them — until every slab is resident
        with _spans.span(
            "sharded.stage", shards=self.n_shards, dtype=serve_dtype,
            item_rows_padded=i_p,
            item_tile=_rp.pick_item_tile(i_p // self.n_shards),
        ):
            with _spans.span("sharded.stage.pad"):
                # inverse norms (from the f32 rows) serve the cosine
                # verbs off the same slab; i_p is col-shardable by
                # construction
                iinv = _rp.inv_norms_np(itf, i_p)
                us = isc = None
                if serve_dtype == "int8":
                    uf, us = _rp.quantize_rows_np(uf)
                    itf, isc = _rp.quantize_rows_np(itf)
                    # a pad row's scale is a zero row's: 1.0
                    isc = np.concatenate([
                        isc, np.ones(i_p - isc.shape[0], isc.dtype)
                    ])
            with _spans.span("sharded.stage.transfer") as sp:
                uscale = iscale = None
                uf_dev = shard_rows(mesh, uf)
                # the item pad rides the put: only the last shard's
                # slab is copied to add its zero rows
                itf_dev = shard_rows(mesh, itf, pad_to=i_p)
                if serve_dtype == "int8":
                    uscale = shard_rows(mesh, us[:, None])
                    iscale = self._put_cols(
                        np.ascontiguousarray(isc[None, :])
                    )
                elif serve_dtype == "bf16":
                    uf_dev = uf_dev.astype(jnp.bfloat16)
                    itf_dev = itf_dev.astype(jnp.bfloat16)
                self._state = jax.block_until_ready(_ShardState(
                    uf=uf_dev, itf=itf_dev, uscale=uscale, iscale=iscale,
                    iinv=self._put_cols(iinv),
                ))
                sp.attrs["bytes"] = int(self.device_bytes()["total"])

    def _put_cols(self, arr: np.ndarray):
        return jax.device_put(
            arr, NamedSharding(self.mesh, P(None, MODEL_AXIS))
        )

    def _staged_bytes_estimate(self, uf: np.ndarray, itf: np.ndarray) -> int:
        """LOGICAL staged bytes for the budget gate: dtype cells plus
        scale/inverse-norm vectors, excluding the tile-pad quantum —
        pad waste is bounded by `pad_items`: under ITEM_PAD rows a
        shard, or PAD_WASTE (1.6 %) of a slab where a wider tile was
        taken (noise at the catalog scales the budget gate exists for)
        — and must not refuse a tiny catalog that plainly fits."""
        u_p = pad_rows_to_shards(self.n_users, self.n_shards)
        i_p = pad_rows_to_shards(self.n_items, self.n_shards)
        cell = SERVE_DTYPE_BYTES[self.serve_dtype]
        total = (u_p + i_p) * self.rank * cell + i_p * 4  # + inv norms
        if self.serve_dtype == "int8":
            total += (u_p + i_p) * 4  # scale vectors
        return total

    @classmethod
    def from_factors(
        cls,
        factors: Any,  # models.als.ALSFactors
        mesh: Optional[jax.sharding.Mesh] = None,
        device_budget_bytes: Optional[float] = None,
        serve_dtype: str = "f32",
        serve_mode: str = "auto",
    ) -> "ShardedRuntime":
        return cls(
            factors.user_factors,
            factors.item_factors,
            user_vocab=factors.user_vocab,
            item_vocab=factors.item_vocab,
            params=factors.params,
            mesh=mesh,
            device_budget_bytes=device_budget_bytes,
            serve_dtype=serve_dtype,
            serve_mode=serve_mode,
        )

    # -- reader leases -----------------------------------------------------
    @contextlib.contextmanager
    def _lease(self):
        """Read lease around a serving dispatch, yielding ONE atomic
        snapshot of the resident state (value/scale/norm arrays can
        never tear). The donated publish drains leases before aliasing
        the resident slabs. Writer priority: new leases wait out a
        pending donate (one scatter dispatch — microseconds) so the
        drain always terminates."""
        with self._reader_cv:
            while self._writer_waiting:
                self._reader_cv.wait(timeout=0.1)
            if self._poisoned:
                raise RuntimeError(
                    "sharded runtime poisoned by a failed donated "
                    "publish — restage (ShardedRuntime.from_factors)"
                )
            self._readers += 1
            st = self._state
        try:
            yield st
        finally:
            with self._reader_cv:
                self._readers -= 1
                self._reader_cv.notify_all()

    # -- serving -----------------------------------------------------------
    def recommend(
        self,
        user_indices: np.ndarray,
        k: int,
        exclude_mask: Optional[np.ndarray] = None,  # (B, n_items) bool
        exclude_rows: Optional[np.ndarray] = None,  # (B, E) int, -1 pad
    ) -> tuple[np.ndarray, np.ndarray]:
        """Global top-k items per user from the sharded state; same
        contract as models.als.recommend (scores, item_indices).

        The exclusion crosses the chips in the smaller of its two
        encodings, chosen from the input's shape alone (the one-chip
        tier's rule, models/als.py `_exclusion_device_args`): a row
        list no wider than the kernel's `ROWLIST_MAX` ships as it is,
        (B, E) int32 replicated — a blacklist of 1-8 ids is 32 B a
        query — and each shard renumbers it into its own slab's rows;
        a dense mask, or a wider list, ships as (B, I_p/32) packed
        words column-sharded over the mesh (1/32 the f32-equivalent
        bytes, and 2.46 MB a query at 19.7 M items)."""
        from predictionio_tpu.ops.recommend_pallas import ROWLIST_MAX

        k = min(int(k), self.n_items)
        rows_np = np.asarray(user_indices, np.int32)
        # the three phases of a sharded batch are spans (ISSUE 27): the
        # host's preparation of the exclusion that ships, the collective
        # program with its puts until the answers are ready, the copies
        # back. `form` names the WIRE form: "rows" = shipped as ids,
        # "mask" = shipped as words, "none"
        if exclude_mask is not None:
            form = "mask"
        elif exclude_rows is None:
            form = "none"
        else:
            wide = np.shape(exclude_rows)[1] > ROWLIST_MAX
            form = "mask" if wide else "rows"
        shipped = None  # the ids (form "rows") or the words ("mask")
        nbytes = 0
        if form != "none":
            with _spans.span("sharded.pack_exclusions", form=form) as sp:
                if form == "rows":
                    shipped = np.ascontiguousarray(exclude_rows, np.int32)
                elif exclude_mask is not None:
                    shipped = self._pack_mask(exclude_mask)
                else:
                    shipped = self._pack_rows(exclude_rows)
                nbytes = int(shipped.nbytes)
                sp.attrs["rows"] = int(shipped.shape[0])
                sp.attrs["bytes"] = nbytes
        with _spans.span(
            "sharded.dispatch", batch=len(rows_np), shards=self.n_shards,
            form=form, exclusion_bytes=nbytes,
        ):
            with self._lease() as st, _collective_guard(self.mesh):
                # the puts are a child span, held until the arrays are
                # resident: words are re-laid on the host for the column-
                # sharded put before they cross, and that is not the
                # program's time (the program cannot start without them);
                # query rows and a row list go replicated, as they are
                with _spans.span(
                    "sharded.dispatch.put", bytes=nbytes + rows_np.nbytes
                ):
                    rows, excl = jax.device_put(
                        (rows_np, shipped if form == "rows" else None),
                        NamedSharding(self.mesh, P()),
                    )
                    bits = (
                        self._put_cols(shipped) if form == "mask" else None
                    )
                    jax.block_until_ready((rows, excl, bits))
                vals, idx = jax.block_until_ready(_sharded_recommend(
                    rows, st.uf, st.itf, st.uscale, st.iscale, bits, excl,
                    k=k, n_items=self.n_items, mesh=self.mesh,
                    mode=self.serve_mode,
                ))
            # words go where they came: dropping the last reference
            # unmaps up to 157 MB of host pages and frees the device's
            # copy, which would else fall, unnamed, at this call's return
            if form == "mask":
                with _spans.span("sharded.dispatch.release"):
                    del shipped, bits
        with _spans.span("sharded.copy_back"):
            return np.asarray(vals), np.asarray(idx)

    def _pack_rows(self, exclude_rows) -> np.ndarray:
        """A row list too wide for the kernel's compare chain (over
        `ROWLIST_MAX`) scatters its ids straight into packed words —
        never a dense (B, n_items) intermediate, which at the catalog
        scales this tier exists for would dwarf the list itself."""
        ex = np.asarray(exclude_rows, np.int64)
        i_p = int(self._state.itf.shape[0])
        words = np.zeros((ex.shape[0], i_p // 32), np.uint32)
        b_idx, e_idx = np.nonzero((ex >= 0) & (ex < self.n_items))
        if len(b_idx):
            ids = ex[b_idx, e_idx]
            np.bitwise_or.at(
                words, (b_idx, ids >> 5),
                np.uint32(1) << (ids & 31).astype(np.uint32),
            )
        return words.view(np.int32)

    def _pack_mask(self, exclude_mask) -> np.ndarray:
        """Bool exclusion mask → bit-packed words at the sharded item
        width, shipped column-sharded over the mesh — 1/32 the
        f32-equivalent mask bytes on the wire and in HBM (ISSUE 14)."""
        from predictionio_tpu.ops.recommend_pallas import pack_mask_np

        i_p = int(self._state.itf.shape[0])
        return pack_mask_np(np.asarray(exclude_mask, bool), i_p)

    def similar_vectors(
        self,
        vectors: np.ndarray,  # (B, K) query vectors (e.g. basket means)
        k: int,
        exclude_mask: Optional[np.ndarray] = None,  # (B, n_items) bool
    ) -> tuple[np.ndarray, np.ndarray]:
        """Cosine top-k against arbitrary query vectors — the
        similarproduct/itemsim basket query served from the sharded
        state (ISSUE 11 satellite)."""
        k = min(int(k), self.n_items)
        vecs = jnp.asarray(np.asarray(vectors, np.float32))
        bits = (
            None if exclude_mask is None
            else self._put_cols(self._pack_mask(exclude_mask))
        )
        with self._lease() as st, _collective_guard(self.mesh):
            vals, idx = jax.block_until_ready(_sharded_similar_vecs(
                vecs, st.itf, st.iscale, st.iinv, bits,
                k=k, n_items=self.n_items, mesh=self.mesh,
                mode=self.serve_mode,
            ))
        return np.asarray(vals), np.asarray(idx)

    def similar_items(
        self,
        item_indices: np.ndarray,
        k: int,
        exclude_self: bool = True,
    ) -> tuple[np.ndarray, np.ndarray]:
        k = min(int(k), self.n_items)
        rows = jnp.asarray(np.asarray(item_indices, np.int32))
        with self._lease() as st, _collective_guard(self.mesh):
            vals, idx = jax.block_until_ready(_sharded_similar(
                rows, st.itf, st.iscale, st.iinv, None,
                k=k, n_items=self.n_items, mesh=self.mesh,
                exclude_self=exclude_self, mode=self.serve_mode,
            ))
        return np.asarray(vals), np.asarray(idx)

    def fold_in_rows(
        self,
        edges: Sequence[Sequence[tuple[int, float]]],
        params: Any,  # models.als.ALSParams
        side: str = "user",
    ) -> np.ndarray:
        """Sharded single-side fold-in (the online consumer's solve):
        per dirty row, solve its system against the FIXED opposite
        sharded matrix; returns the (R, K) solved factors. Bucketing
        mirrors models.als.fold_in_rows so streaming ticks reuse a
        handful of compiled programs."""
        from predictionio_tpu.models.als import _fold_edge_bucket
        from predictionio_tpu.utils.bucket import batch_bucket

        if not edges:
            return np.zeros((0, self.rank), np.float32)
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
        with self._lease() as st:
            if side == "user":
                fixed, scale, scale_cols = st.itf, st.iscale, True
            else:
                fixed, scale, scale_cols = st.uf, st.uscale, False
            with _collective_guard(self.mesh):
                solved = jax.block_until_ready(_sharded_fold_in(
                    fixed, scale,
                    jnp.asarray(idx), jnp.asarray(val), jnp.asarray(ok),
                    jnp.float32(params.lambda_), jnp.float32(params.alpha),
                    implicit=params.implicit_prefs,
                    cg_iterations=params.cg_iterations,
                    mesh=self.mesh,
                    scale_cols=scale_cols,
                ))
        return np.asarray(solved)[:r_real]

    # -- state updates -----------------------------------------------------
    def update_user_rows(
        self, rows: np.ndarray, values: np.ndarray,
        n_users: Optional[int] = None,
    ) -> None:
        """Publish dirty user rows (f32 values) into the resident
        sharded slab: re-quantizes ONLY these rows for int8 slabs and
        donates the slab into the row write once in-flight readers
        drain — no full restage, no host round-trip (ISSUE 14,
        direction-1 item (c)). `n_users`/`n_items` carry the fold's
        new LIVE vocab extent: within-pad growth must raise the live
        count, or the grown rows stay masked dead under every verb's
        live-count gate (the count is a static jit arg on this tier,
        so a growth tick retraces — amortized like the pad itself)."""
        self._publish("user", rows, values, new_count=n_users)

    def update_item_rows(
        self, rows: np.ndarray, values: np.ndarray,
        n_items: Optional[int] = None,
    ) -> None:
        self._publish("item", rows, values, new_count=n_items)

    def rows_within_extent(self, side: str, rows) -> bool:
        """True when a dirty-row publish for `side` fits the padded
        shard extent — the pre-check a fold-in carry runs on BOTH
        sides BEFORE mutating either, so a grown side can never leave
        the live runtime half-updated (ResidentServing.adopt)."""
        rows = np.asarray(rows, np.int64)
        st = self._state
        table = st.uf if side == "user" else st.itf
        return not rows.size or int(rows.max()) < int(table.shape[0])

    def _publish(self, side: str, rows, values, new_count=None) -> None:
        from predictionio_tpu.ops import recommend_pallas as _rp

        rows = np.asarray(rows, np.int32)
        values = np.asarray(values, np.float32)
        if not self.rows_within_extent(side, rows):
            raise ValueError(
                "row update beyond the padded shard extent — vocab "
                "growth needs a rebuild (amortized like the online "
                "fold-in's factor growth), not an in-place set"
            )
        if not rows.size:
            return
        # host prep: quantize/norm ONLY the dirty rows
        if self.serve_dtype == "int8":
            q, s = _rp.quantize_rows_np(values)
            vals_dev = jnp.asarray(q)
            scale_dev = jnp.asarray(s)
        else:
            vals_dev = jnp.asarray(values)
            scale_dev = None
        inv_dev = (
            jnp.asarray(_rp.inv_norms_np(values)[0])
            if side == "item" else None
        )
        rows_dev = jnp.asarray(rows)
        with self._lock:  # one publisher at a time
            st = self._state
            donate = self._drain_readers()
            try:
                srows = (
                    _scatter_rows_donated if donate else _scatter_rows
                )
                scols = (
                    _scatter_cols_donated if donate else _scatter_cols
                )
                # the guard also covers the COW fallback: its scatters
                # run WHILE readers keep serving, and an unserialized
                # overlap of two cpu collectives is exactly the pool-
                # starvation wedge the lock exists for. block before
                # releasing so no scatter is still in flight when the
                # next reader launches.
                with _collective_guard(self.mesh):
                    if side == "user":
                        uf = srows(
                            st.uf, rows_dev, vals_dev, mesh=self.mesh
                        )
                        uscale = st.uscale
                        if scale_dev is not None:
                            uscale = srows(
                                st.uscale, rows_dev, scale_dev[:, None],
                                mesh=self.mesh,
                            )
                        new = st._replace(uf=uf, uscale=uscale)
                    else:
                        itf = srows(
                            st.itf, rows_dev, vals_dev, mesh=self.mesh
                        )
                        iscale = st.iscale
                        if scale_dev is not None:
                            iscale = scols(
                                st.iscale, rows_dev, scale_dev,
                                mesh=self.mesh,
                            )
                        iinv = scols(
                            st.iinv, rows_dev, inv_dev, mesh=self.mesh
                        )
                        new = st._replace(
                            itf=itf, iscale=iscale, iinv=iinv
                        )
                    new = jax.block_until_ready(new)
                # ONE atomic swap: readers see either the old or the
                # new state tuple, never a torn value/scale pair (the
                # COW fallback admits readers during these scatters)
                self._state = new
                if new_count is not None:
                    # within-pad vocab growth: raise the LIVE extent or
                    # the grown rows stay dead under the verbs' live-
                    # count gates (a growth tick retraces the static-
                    # count jits — amortized like the pad headroom)
                    if side == "user":
                        self.n_users = max(self.n_users, int(new_count))
                    else:
                        self.n_items = max(self.n_items, int(new_count))
            except BaseException:
                if donate:
                    # the donated scatters may have consumed buffers the
                    # un-swapped state still references — every further
                    # dispatch against them would crash with an opaque
                    # XLA error. Poison the runtime so leases fail FAST
                    # and callers restage (ResidentServing.adopt drops the
                    # carry; the predecessor is mid-replacement anyway).
                    self._poisoned = True
                    log.exception(
                        "donated sharded publish failed mid-write — "
                        "runtime poisoned; callers must restage"
                    )
                raise
            finally:
                if donate:
                    with self._reader_cv:
                        self._writer_waiting = False
                        self._reader_cv.notify_all()

    def _drain_readers(self) -> bool:
        """Gate new leases and wait for in-flight ones; True = drained
        (donation safe), False = timed out (caller must COW). Always
        leaves `_writer_waiting` True on success — the caller clears it
        after the donated writes land."""
        import time as _time

        deadline = _time.monotonic() + _DONATE_DRAIN_S
        with self._reader_cv:
            self._writer_waiting = True
            while self._readers > 0:
                remaining = deadline - _time.monotonic()
                if remaining <= 0:
                    self._writer_waiting = False
                    self._reader_cv.notify_all()
                    log.warning(
                        "sharded publish: readers did not drain in "
                        "%.1fs — falling back to copy-on-write",
                        _DONATE_DRAIN_S,
                    )
                    return False
                self._reader_cv.wait(timeout=remaining)
            return True

    # -- accounting --------------------------------------------------------
    def device_bytes(self) -> dict[str, float]:
        st = self._state
        total = float(st.uf.nbytes + st.itf.nbytes + st.iinv.nbytes)
        if st.uscale is not None:
            total += float(st.uscale.nbytes + st.iscale.nbytes)
        return {
            "total": total,
            "per_shard": total / self.n_shards,
            "shards": float(self.n_shards),
        }

    def info(self) -> dict[str, Any]:
        from predictionio_tpu.ops.recommend_pallas import pick_item_tile

        b = self.device_bytes()
        i_p = int(self._state.itf.shape[0])
        return {
            "shards": self.n_shards,
            "devices": [
                str(d) for d in self.mesh.devices.reshape(-1)
            ],
            "n_users": self.n_users,
            "n_items": self.n_items,
            "rank": self.rank,
            "serve_dtype": self.serve_dtype,
            "serve_mode": self.serve_mode or "xla",
            # the staged item rows, pad included, and the kernel tile
            # that divides a shard's slab (0 on the XLA path's 32-row
            # quantum where none does)
            "item_rows_padded": i_p,
            "item_tile": pick_item_tile(i_p // self.n_shards),
            "resident_bytes_total": b["total"],
            "resident_bytes_per_shard": b["per_shard"],
        }

    # the tenant cache's device-bytes walk finds the state tuple via
    # __dict__: jax arrays report addressable-shard bytes there, so a
    # cached sharded runtime is charged one SHARD, not the catalog
    @property
    def models(self):  # EngineRuntime-walk compatibility
        st = self._state
        return (st.uf, st.itf)
