"""Chip-side parity of the three Pallas kernels against their XLA forms.

tier-1 proves the kernels in INTERPRET mode on the CPU; this script runs
the same comparisons through the Mosaic lowering on an attached TPU — what
tests/test_recommend_pallas.py, tests/test_fused_serving.py,
tests/test_windowed_pallas.py and tests/test_dense_pallas.py check, with
the kernel mode "tpu". It is not collected by pytest (the suite is pinned
to the CPU); run it on the chip:

    chiprun -- python tests/chip_parity.py        # writes chiprun_out/

`--interpret` walks the same checks through the interpreter on the CPU —
a rehearsal of the script, not a chip result.

What "agrees" means here: the fused kernel and the XLA two-step run on the
SAME device from the SAME operands, so integer paths must match exactly
and float paths may differ only by how each rounds its matmul. Every
comparison reports the largest deviation it saw beside the bound it was
held to. What a v5e showed (PR 21, PERF.md Findings): int8, bf16, cosine
`similar`, the CCO tail, ties and both mask forms agree exactly or to a
few f32 ulps; f32 agrees bit-for-bit at B = 8 and 64, where both run the
MXU on bf16-rounded operands (at worst 2^-8.1 of the row's largest
sum|q_k x_k| off numpy), and
differs at B = 1, where XLA lowers the product exactly and the kernel
does not.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from predictionio_tpu.data.store.bimap import BiMap  # noqa: E402
from predictionio_tpu.models import als, cco  # noqa: E402
from predictionio_tpu.ops import dense, dense_pallas  # noqa: E402
from predictionio_tpu.ops import recommend_pallas as rp  # noqa: E402
from predictionio_tpu.ops.topk import NEG_INF  # noqa: E402
from predictionio_tpu.ops.windowed import plan_windows, windowed_gram_b  # noqa: E402

# A default-precision f32 dot on the TPU's MXU multiplies bf16 operands
# (each within 2^-9 of its value if rounded, 2^-8 if truncated) and
# accumulates in f32: a score is within 2^-8..2^-7 * sum_k |q_k x_k| of
# the exact one. Deviations below are reported as a fraction of
# F32_BOUND * max_i sum_k |q_k x_ik|. bf16 storage adds nothing (products
# of bf16 values are exact in f32); int8 is integer arithmetic and exact.
F32_BOUND = 2.0 ** -7
RESULTS: list[dict] = []


def record(name: str, ok: bool, **detail) -> None:
    RESULTS.append({"check": name, "ok": bool(ok), **detail})
    shown = " ".join(f"{k}={v}" for k, v in detail.items())
    print(f"{'ok  ' if ok else 'FAIL'} {name} {shown}", flush=True)


def _factors(rng, u, i, k=10):
    return als.ALSFactors(
        user_factors=rng.standard_normal((u, k)).astype(np.float32),
        item_factors=rng.standard_normal((i, k)).astype(np.float32),
        user_vocab=BiMap({}), item_vocab=BiMap({}),
    )


def _rank_slack(ref_scores, got_idx, kth, bound):
    """How far below the reference's k-th best the served items sit, in
    units of the bound (<= 1 passes)."""
    short = kth[:, None] - np.take_along_axis(ref_scores, got_idx, axis=1)
    return float(np.max(short / bound))


def check_recommend(mode: str, n_users: int, n_items: int, k: int, tag: str):
    """fused kernel vs XLA two-step through als.recommend_serving — every
    dtype x exclusion form x batch bucket; f32 also against numpy."""
    rng = np.random.RandomState(0)
    f = _factors(rng, n_users, n_items)
    absdot = np.abs(f.user_factors) @ np.abs(f.item_factors).T
    exact = f.user_factors @ f.item_factors.T
    for dtype in ("f32", "bf16", "int8"):
        sv_k = dataclasses.replace(
            als.stage_serving(f, serve_dtype=dtype), mode=mode
        )
        sv_x = dataclasses.replace(sv_k, mode=None)
        for b in (1, 8, 64):
            rows = rng.randint(0, n_users, b)
            mask = rng.rand(b, n_items) < 0.3
            ex = np.full((b, 8), -1, np.int32)
            for r in range(b):
                ex[r, :5] = rng.choice(n_items, 5, replace=False)
            for kind, kw in (
                ("none", {}), ("bits", {"exclude_mask": mask}),
                ("rows", {"exclude_rows": ex}),
            ):
                vk, ik = als.recommend_serving(sv_k, rows, k, **kw)
                vx, ix = als.recommend_serving(sv_x, rows, k, **kw)
                live = vx > NEG_INF / 2
                same = float(np.mean(ik[live] == ix[live]))
                bound = F32_BOUND * np.max(absdot[rows], axis=1)
                dv = np.max(
                    np.abs(np.where(live, vk - vx, 0.0)) / bound[:, None]
                )
                masked_ok = True
                if kind == "bits":
                    masked_ok = not mask[np.arange(b)[:, None], ik][
                        vk > NEG_INF / 2].any()
                if dtype == "int8":
                    ok = same == 1.0 and np.array_equal(vk, vx)
                else:
                    ok = dv <= 1.0 and masked_ok
                detail = dict(idx_equal=round(same, 4),
                              dv_over_bound=float(f"{dv:.3e}"))
                if dtype == "f32" and kind == "none":
                    # against the exact numpy scores
                    ref_k = np.take_along_axis(exact[rows], ik, axis=1)
                    e = np.max(np.abs(vk - ref_k) / bound[:, None])
                    kth = np.sort(exact[rows], axis=1)[:, -k]
                    slack = _rank_slack(exact[rows], ik, kth,
                                        2 * bound[:, None])
                    ok = ok and e <= 1.0 and slack <= 1.0
                    ref_x = np.take_along_axis(exact[rows], ix, axis=1)
                    ex_ = np.max(np.abs(vx - ref_x) / bound[:, None])
                    detail.update(numpy_err_over_bound=float(f"{e:.3e}"),
                                  xla_numpy_err_over_bound=float(f"{ex_:.3e}"),
                                  numpy_rank_slack=round(slack, 4))
                record(f"recommend[{tag}] {dtype} {kind} B={b} k={k}",
                       ok, **detail)


def check_recommend_edges(mode: str):
    """Crafted ties, a fully-masked row, k == n, packed == row list."""
    interp = mode == "interpret"

    def fused(uf, itf, k, mask=None):
        i_p = rp.pad_items(itf.shape[0])
        pad = np.zeros((i_p, itf.shape[1]), np.float32)
        pad[: itf.shape[0]] = itf
        bits = None if mask is None else jnp.asarray(rp.pack_mask_np(mask, i_p))
        return [np.asarray(a) for a in rp.fused_recommend_topk(
            jnp.asarray(uf), jnp.asarray(pad), None, None, bits,
            k=k, n_items=itf.shape[0], interpret=interp,
        )]

    # equal scores everywhere (small integers: exact at any precision) —
    # lowest index first, across the 128-row tile boundary
    uf = np.ones((2, 4), np.float32)
    itf = np.tile(np.array([[1, 0, 0, 0]], np.float32), (260, 1))
    v, ix = fused(uf, itf, 140)
    record("ties: all-equal scores keep lax.top_k order",
           np.array_equal(ix, np.tile(np.arange(140), (2, 1))))
    # duplicated rows straddling the tile boundary: each score twice
    rng = np.random.RandomState(3)
    base = rng.standard_normal((130, 6)).astype(np.float32)
    v, ix = fused(rng.standard_normal((3, 6)).astype(np.float32),
                  np.concatenate([base, base]), 50)
    pairs = ix.reshape(3, 25, 2)
    record("ties: duplicate rows come out lowest index first",
           bool(np.all(pairs[:, :, 1] == pairs[:, :, 0] + 130)))
    mask = np.zeros((2, 200), bool)
    mask[1, :] = True
    v, ix = fused(rng.standard_normal((2, 4)).astype(np.float32),
                  rng.standard_normal((200, 4)).astype(np.float32), 6, mask)
    record("fully-masked row returns NEG_INF at indices 0..k-1",
           bool(np.all(v[1] == NEG_INF))
           and np.array_equal(ix[1], np.arange(6)))
    v, ix = fused(rng.standard_normal((1, 8)).astype(np.float32),
                  rng.standard_normal((7, 8)).astype(np.float32), 7)
    record("k == n_items drains the catalog",
           sorted(ix[0].tolist()) == list(range(7))
           and bool(np.all(np.diff(v[0]) <= 0)))
    f = _factors(rng, 50, 300)
    sv = dataclasses.replace(als.stage_serving(f), mode=mode)
    ex = np.full((8, 8), -1, np.int32)
    mask = np.zeros((8, 300), bool)
    for r in range(8):
        ex[r, :5] = rng.choice(300, 5, replace=False)
        mask[r, ex[r, :5]] = True
    vm, im = als.recommend_serving(sv, np.arange(8), 10, exclude_mask=mask)
    vr, ir = als.recommend_serving(sv, np.arange(8), 10, exclude_rows=ex)
    record("packed words == row list (same kernel arithmetic)",
           np.array_equal(im, ir) and np.array_equal(vm, vr))


def check_similar(mode: str):
    rng = np.random.RandomState(20)
    f = _factors(rng, 50, 300)
    for dtype in ("f32", "bf16", "int8"):
        sv_k = dataclasses.replace(
            als.stage_serving(f, serve_dtype=dtype), mode=mode
        )
        sv_x = dataclasses.replace(sv_k, mode=None)
        vk, ik = als.similar_serving(sv_k, np.arange(8), 11)
        vx, ix = als.similar_serving(sv_x, np.arange(8), 11)
        dv = float(np.max(np.abs(vk - vx)))  # cosines: |score| <= 1
        self_out = all(r not in ik[r] for r in range(8))
        ok = self_out and (
            (np.array_equal(ik, ix) and dv <= 1e-6) if dtype == "int8"
            else dv <= F32_BOUND * 10 ** 0.5
        )
        record(f"similar {dtype} kernel vs XLA", ok,
               idx_equal=round(float(np.mean(ik == ix)), 4),
               max_dv=float(f"{dv:.3e}"))


def check_cco(mode: str):
    """The precomputed-score tail: no matmul in the kernel, so the fused
    and XLA tails must agree exactly — row-list and packed exclusion."""
    rng = np.random.RandomState(25)
    tables, hists = [], []
    for j in (120, 80):
        tables.append((
            rng.randint(-1, j, (500, 20)).astype(np.int32),
            np.abs(rng.standard_normal((500, 20))).astype(np.float32), j,
        ))
        hists.append(rng.randint(-1, j, (8, 16)).astype(np.int32))
    staged = cco.stage_correlators(tables)
    plan = cco.plan_windows(staged, hists)
    for width in (32, 128):
        ex = np.full((8, width), -1, np.int32)
        n_ids = 12 if width == 32 else 70  # beyond ROWLIST_MAX: words
        for b in range(8):
            ex[b, :n_ids] = rng.choice(500, n_ids, replace=False)
        exclude = cco.exclusion_of(
            [[int(i) for i in row if i >= 0] for row in ex], 8,
            staged.rows_padded)
        v0, i0 = cco.batch_score_topk(staged, plan, exclude, 8, 17, mode="off")
        v1, i1 = cco.batch_score_topk(staged, plan, exclude, 8, 17, mode=mode)
        record(f"cco tail width={width} kernel vs XLA",
               np.array_equal(i0, i1) and np.allclose(v0, v1, rtol=1e-6),
               idx_equal=round(float(np.mean(i0 == i1)), 4),
               max_dv=float(f"{np.max(np.abs(v0 - v1)):.3e}"))


def check_windowed(mode: str):
    """ops/windowed_pallas.block_partials vs the XLA one-hot scan, then a
    whole small train through each."""
    rng = np.random.default_rng(7)
    for n_edges in (500, 5000, 70_000):
        src = rng.integers(0, 60, n_edges)
        dst = np.sort(rng.integers(0, 300, n_edges))
        vals = rng.uniform(0.5, 5.0, n_edges).astype(np.float32)
        plan = plan_windows(dst, 300)
        args = (
            jnp.asarray(rng.normal(size=(60, 8)).astype(np.float32)),
            jnp.asarray(plan.take(src.astype(np.int32))).astype(jnp.int32),
            jnp.asarray(plan.take(vals)),
            jnp.asarray(plan.take((1.0 + vals).astype(np.float32))),
            jnp.asarray(plan.chunked_local()),
            jnp.asarray(plan.block_window),
            plan.n_windows,
        )
        bx, gx = [np.asarray(a) for a in windowed_gram_b(*args, pallas=None)]
        bk, gk = [np.asarray(a) for a in windowed_gram_b(*args, pallas=mode)]
        eb = float(np.max(np.abs(bk - bx)) / np.max(np.abs(bx)))
        eg = float(np.max(np.abs(gk - gx)) / np.max(np.abs(gx)))
        record(f"windowed block_partials E={n_edges} kernel vs XLA",
               eb <= F32_BOUND and eg <= F32_BOUND,
               b_err=float(f"{eb:.3e}"), gram_err=float(f"{eg:.3e}"))
    n_users, n_items, n_edges = 300, 180, 5000
    rows = rng.integers(0, n_users, n_edges).astype(np.int32)
    cols = rng.integers(0, n_items, n_edges).astype(np.int32)
    vals = rng.uniform(0.5, 5.0, n_edges).astype(np.float32)
    p = als.ALSParams(rank=8, iterations=4)
    outs = {}
    for m in (None, mode):
        st = als.stage_windowed(rows, cols, vals, n_users, n_items, p)
        st.static_kwargs["pallas_mode"] = m
        outs[m] = st.factors(*st.run())
    corr = min(
        float(np.corrcoef(outs[mode][s].ravel(), outs[None][s].ravel())[0, 1])
        for s in (0, 1)
    )
    record("windowed train kernel vs XLA factors",
           corr > 0.999 and all(np.isfinite(a).all() for a in outs[mode]),
           min_corr=round(corr, 6))


def check_dense(mode: str):
    """ops/dense_pallas (opt-in, PIO_PALLAS_DENSE) vs ops/dense int8."""
    rng = np.random.RandomState(5)
    n_r, n_c, k = 2048, 2560, 10
    r = np.zeros((n_r, n_c), np.int8)
    hit = rng.rand(n_r, n_c) < 0.01
    r[hit] = rng.randint(1, 6, int(hit.sum()))
    y = rng.standard_normal((n_c, k)).astype(np.float32)
    x = rng.standard_normal((n_r, k)).astype(np.float32)
    rt, ct = dense_pallas.pick_tiles(n_r, n_c)
    ascale = jnp.asarray([1.0], jnp.float32)
    for name, fused, ref, fixed in (
        ("row", dense_pallas.fused_row_pass, dense.dense_row_pass, y),
        ("col", dense_pallas.fused_col_pass, dense.dense_col_pass, x),
    ):
        z = (fixed[:, :, None] * fixed[:, None, :]).reshape(len(fixed), k * k)
        bk, ck = [np.asarray(a) for a in fused(
            jnp.asarray(r), jnp.asarray(fixed), jnp.asarray(z), ascale,
            implicit=True, interpret=(mode == "interpret"),
            row_tile=rt, col_tile=ct,
        )]
        bx, cx = [np.asarray(a) for a in ref(
            jnp.asarray(r), jnp.asarray(fixed), implicit=True, alpha=1.0,
            dense_dtype="int8", scale=1.0,
        )]
        eb = float(np.max(np.abs(bk - bx)) / np.max(np.abs(bx)))
        ec = float(np.max(np.abs(ck - cx)) / np.max(np.abs(cx)))
        record(f"dense {name} pass kernel vs XLA (both bf16 operands)",
               eb <= F32_BOUND and ec <= F32_BOUND,
               b_err=float(f"{eb:.3e}"), corr_err=float(f"{ec:.3e}"))


def check_sharded(mode: str):
    if len(jax.devices()) < 2:
        record("sharded twin", True, skipped="one device visible")
        return
    from predictionio_tpu.fleet.runtime import ShardedRuntime

    rng = np.random.RandomState(31)
    uf = rng.standard_normal((400, 10)).astype(np.float32)
    itf = rng.standard_normal((5700, 10)).astype(np.float32)
    absmax = np.max(np.abs(uf[:5]) @ np.abs(itf).T, axis=1)
    mask = rng.rand(5, 5700) < 0.3
    for dtype in ("f32", "bf16", "int8"):
        rk = ShardedRuntime(uf, itf, serve_dtype=dtype, serve_mode=mode)
        rx = ShardedRuntime(uf, itf, serve_dtype=dtype, serve_mode="off")
        for kind, kw in (("none", {}), ("bits", {"exclude_mask": mask})):
            vk, ik = rk.recommend(np.arange(5), 9, **kw)
            vx, ix = rx.recommend(np.arange(5), 9, **kw)
            dv = float(np.max(np.abs(vk - vx) / (F32_BOUND * absmax[:, None])))
            ok = (np.array_equal(ik, ix) and np.array_equal(vk, vx)
                  if dtype == "int8" else dv <= 1.0)
            record(f"sharded[{rk.n_shards}] {dtype} {kind} kernel vs XLA",
                   ok, idx_equal=round(float(np.mean(ik == ix)), 4),
                   dv_over_bound=float(f"{dv:.3e}"))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--interpret", action="store_true")
    ap.add_argument("--out", default="chiprun_out/chip_parity.json")
    ap.add_argument(
        "--only", default="",
        help="run one family: edges|recommend|similar|cco|windowed|"
             "dense|sharded",
    )
    args = ap.parse_args()
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if args.interpret:
        mode = "interpret"
    elif device["platform"] != "tpu":
        print(f"chip_parity: no TPU (jax sees {device}); use --interpret "
              "to rehearse on the CPU", file=sys.stderr)
        return 2
    else:
        mode = "tpu"
    print(f"chip_parity: mode={mode} device={device}", flush=True)
    def recommend(mode):
        check_recommend(mode, 50, 300, 128, "small")
        if not args.interpret:  # the interpreter needs minutes at this width
            check_recommend(mode, 4096, 26_744, 128, "ml20m")

    families = {
        "edges": check_recommend_edges, "recommend": recommend,
        "similar": check_similar, "cco": check_cco,
        "windowed": check_windowed, "dense": check_dense,
        "sharded": check_sharded,
    }
    if args.only and args.only not in families:
        ap.error(f"--only must be one of {sorted(families)}")
    for name, check in families.items():
        if not args.only or args.only == name:
            check(mode)
    failed = [r for r in RESULTS if not r["ok"]]
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"mode": mode, "device": device, "results": RESULTS}, f,
                  indent=1)
    print(f"chip_parity: {len(RESULTS) - len(failed)}/{len(RESULTS)} checks "
          f"hold in mode {mode} on {device}", flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
