"""Dense-W ALS fast path (ops/dense.py + models/als.py dense solvers).

The dense path replaces the windowed edge pass with plain dense matmuls
over a device-resident rating matrix (the below-1%-density TPU move —
see ops/dense.py). These tests pin: pass-level exactness against numpy,
end-to-end agreement with the windowed path, the grid variant, resume,
and the auto-dispatch gate.
"""

import os

import numpy as np
import pytest

from predictionio_tpu.models import als
from predictionio_tpu.ops import dense as dense_ops


def _coo(n_users=300, n_items=180, n_edges=6000, seed=0, signed=False):
    rng = np.random.RandomState(seed)
    rows = rng.randint(0, n_users, n_edges).astype(np.int32)
    cols = rng.randint(0, n_items, n_edges).astype(np.int32)
    key = rows.astype(np.int64) * n_items + cols
    _, idx = np.unique(key, return_index=True)
    rows, cols = rows[idx], cols[idx]
    vals = (rng.randint(1, 11, len(rows)) / 2.0).astype(np.float32)
    if signed:
        vals *= rng.choice([-1.0, 1.0], len(rows)).astype(np.float32)
    return rows, cols, vals


def _pad_dims(n_users, n_items):
    nup = -(-n_users // dense_ops.ROW_BLOCK) * dense_ops.ROW_BLOCK
    nip = -(-n_items // dense_ops.COL_PAD) * dense_ops.COL_PAD
    return nup, nip


def _whole_matrix_scatter(rows, cols, vals, nup, nip, dense_dtype, scale):
    """`densify` as it was before PR 36, the oracle of the block build: one
    2-D scatter of the pairs in the order given, the values rounded on the
    device."""
    import jax.numpy as jnp

    st = dense_ops.storage_dtype(dense_dtype)
    vals = jnp.asarray(vals)
    if dense_dtype == "int8":
        q = jnp.round(vals * jnp.float32(scale)).astype(jnp.int8)
    else:
        q = vals.astype(st)
    return jnp.zeros((nup, nip), st).at[
        jnp.asarray(rows), jnp.asarray(cols)
    ].set(q)


#: (n_users, n_items, pairs, the row blocks that hold pairs and their
#: weights, slots a scatter or None for the module's own)
_BUILD_CASES = {
    # three row blocks, the pairs in shuffled order, several chunks a block
    "shuffled_three_blocks": (5000, 70, 3001, None, 128),
    "an_empty_row_block": (6000, 70, 2000, {0: 0.5, 2: 0.5}, 128),
    "one_block_holds_most": (6000, 70, 4000, {0: 0.03, 1: 0.94, 2: 0.03}, 128),
    "pairs_no_multiple_of_the_chunk": (100, 70, 901, None, 128),
    "fewer_pairs_than_a_chunk": (100, 70, 50, None, 128),
    "no_pairs": (100, 70, 0, None, 128),
    "the_modules_own_chunk": (5000, 300, 70_000, None, None),
}


def _build_case(name):
    nu, ni, n, blocks, chunk = _BUILD_CASES[name]
    rng = np.random.RandomState(len(name))
    if blocks is None:
        key = rng.choice(nu * ni, n, replace=False)
    else:
        parts = []
        for b, share in blocks.items():
            lo = b * dense_ops.ROW_BLOCK
            hi = min(nu, lo + dense_ops.ROW_BLOCK)
            parts.append(
                lo * ni + rng.choice((hi - lo) * ni, int(n * share), replace=False)
            )
        key = rng.permutation(np.concatenate(parts))
    rows = (key // ni).astype(np.int32)
    cols = (key % ni).astype(np.int32)
    # signed half-star steps: exact in int8 at scale 2 and in bf16
    vals = (rng.randint(1, 11, len(key)) / 2.0).astype(np.float32)
    vals *= rng.choice([-1.0, 1.0], len(key)).astype(np.float32)
    if name == "one_block_holds_most":
        assert np.mean(rows // dense_ops.ROW_BLOCK == 1) > 0.9
    return nu, ni, rows, cols, vals, chunk


def _densify(rows, cols, vals, nup, nip, dense_dtype="f32", scale=1.0):
    """The dense matrix of these pairs, as `stage_dense` builds it."""
    import jax.numpy as jnp

    pairs = als._group_unique_pairs(
        rows, cols, vals, nup, nip, dense_dtype, scale
    )
    return dense_ops.densify(
        jnp.asarray(pairs.offsets), jnp.asarray(pairs.values),
        jnp.asarray(pairs.starts), n_rows_p=nup, n_cols_p=nip,
    )


class TestDensePasses:
    """Pass-level exactness (f32 mode) against a per-edge numpy fold."""

    @pytest.mark.parametrize(
        "dense_dtype,scale", [("int8", 2.0), ("bf16", 1.0), ("f32", 1.0)]
    )
    @pytest.mark.parametrize("case", sorted(_BUILD_CASES))
    def test_block_build_equals_numpy_and_the_whole_matrix_scatter(
        self, monkeypatch, case, dense_dtype, scale
    ):
        """The matrix built a row block at a time is `ref[rows, cols] =
        vals · scale`, and to the bit the one the whole-matrix scatter it
        replaced gave: the pairs are unique, so the order they are
        written in cannot show."""
        import jax
        import jax.numpy as jnp

        nu, ni, rows, cols, vals, chunk = _build_case(case)
        nup, nip = _pad_dims(nu, ni)
        build = dense_ops.densify
        if chunk is not None:
            monkeypatch.setattr(dense_ops, "_SLOT_CHUNK", chunk)
            # the jit cache keys on shapes, not on the patched constant:
            # trace the undecorated function afresh
            build = jax.jit(
                dense_ops.densify.__wrapped__.__wrapped__,
                static_argnames=("n_rows_p", "n_cols_p"),
            )
        pairs = als._group_unique_pairs(
            rows, cols, vals, nup, nip, dense_dtype, scale
        )
        r = np.asarray(build(
            jnp.asarray(pairs.offsets), jnp.asarray(pairs.values),
            jnp.asarray(pairs.starts), n_rows_p=nup, n_cols_p=nip,
        ))
        st = dense_ops.storage_dtype(dense_dtype)
        assert r.dtype == st and r.shape == (nup, nip)
        ref = np.zeros((nup, nip), np.float32)
        ref[rows, cols] = (vals * scale).astype(st)
        np.testing.assert_array_equal(r.astype(np.float32), ref)
        old = np.asarray(_whole_matrix_scatter(
            rows, cols, vals, nup, nip, dense_dtype, scale
        ))
        bits = f"u{r.dtype.itemsize}"
        np.testing.assert_array_equal(r.view(bits), old.view(bits))
        per_block = np.bincount(rows // dense_ops.ROW_BLOCK, minlength=1)
        step = dense_ops._SLOT_CHUNK
        assert dense_ops.dead_slots(pairs.starts) == sum(
            -(-n // step) * step - n for n in per_block.tolist()
        )

    @pytest.mark.parametrize("implicit", [True, False])
    @pytest.mark.parametrize("signed", [False, True])
    def test_row_and_col_pass_match_numpy(self, implicit, signed):
        import jax.numpy as jnp

        if not implicit and signed:
            pytest.skip("explicit mode: sign carries through r itself")
        nu, ni, k, alpha = 100, 70, 8, 2.0
        rows, cols, vals = _coo(nu, ni, 900, seed=1, signed=signed)
        rng = np.random.RandomState(2)
        y = rng.randn(ni, k).astype(np.float32)
        x = rng.randn(nu, k).astype(np.float32)
        nup, nip = _pad_dims(nu, ni)
        yp = np.zeros((nip, k), np.float32)
        yp[:ni] = y
        xp = np.zeros((nup, k), np.float32)
        xp[:nu] = x
        r = _densify(rows, cols, vals, nup, nip)

        def w(v):
            if implicit:
                return (1.0 + alpha * abs(v)) * (v > 0), alpha * abs(v)
            return v, 1.0

        b_ref = np.zeros((nu, k))
        g_ref = np.zeros((nu, k, k))
        bc_ref = np.zeros((ni, k))
        gc_ref = np.zeros((ni, k, k))
        for r_, c_, v_ in zip(rows, cols, vals):
            w1, wg = w(v_)
            b_ref[r_] += w1 * y[c_]
            g_ref[r_] += wg * np.outer(y[c_], y[c_])
            bc_ref[c_] += w1 * x[r_]
            gc_ref[c_] += wg * np.outer(x[r_], x[r_])

        b, corr = dense_ops.dense_row_pass(
            r, jnp.asarray(yp), implicit=implicit, alpha=alpha,
            dense_dtype="f32",
        )
        np.testing.assert_allclose(
            np.asarray(b)[:nu], b_ref, rtol=1e-4, atol=1e-4
        )
        np.testing.assert_allclose(
            np.asarray(corr)[:nu].reshape(nu, k, k), g_ref,
            rtol=1e-4, atol=1e-4,
        )
        bc, gc = dense_ops.dense_col_pass(
            r, jnp.asarray(xp), implicit=implicit, alpha=alpha,
            dense_dtype="f32",
        )
        np.testing.assert_allclose(
            np.asarray(bc)[:ni], bc_ref, rtol=1e-4, atol=1e-4
        )
        np.testing.assert_allclose(
            np.asarray(gc)[:ni].reshape(ni, k, k), gc_ref,
            rtol=1e-4, atol=1e-4,
        )


class TestDenseTrain:
    @pytest.mark.parametrize("implicit", [True, False])
    def test_f32_dense_matches_windowed(self, implicit):
        rows, cols, vals = _coo()
        p = als.ALSParams(
            rank=8, iterations=6, implicit_prefs=implicit,
            alpha=2.0, lambda_=0.05,
        )
        ref = als.train(rows, cols, vals, 300, 180, p)  # windowed
        staged = als.stage_dense(
            rows, cols, vals, 300, 180, p, dense_dtype="f32"
        )
        uf, itf = staged.factors(*staged.run())
        # same math, different summation order + truncated CG → small
        # per-element drift compounds over alternating iterations; the
        # implicit operator is well-conditioned (tight), ALS-WR less so
        tol = 2e-3 if implicit else 5e-2
        np.testing.assert_allclose(
            uf, ref.user_factors, rtol=tol, atol=tol
        )
        np.testing.assert_allclose(
            itf, ref.item_factors, rtol=tol, atol=tol
        )

    def test_bf16_dense_is_finite_and_close(self):
        rows, cols, vals = _coo()
        p = als.ALSParams(rank=8, iterations=6, alpha=2.0, lambda_=0.05)
        ref = als.train(rows, cols, vals, 300, 180, p)
        staged = als.stage_dense(
            rows, cols, vals, 300, 180, p, dense_dtype="bf16"
        )
        uf, itf = staged.factors(*staged.run())
        assert np.all(np.isfinite(uf)) and np.all(np.isfinite(itf))
        c = np.corrcoef(uf.ravel(), ref.user_factors.ravel())[0, 1]
        assert c > 0.999

    def test_signed_feedback(self):
        """Dislikes (r<0): conf uses |r|, pref is 0 — dense weights must
        reproduce the windowed path's signed-implicit semantics."""
        rows, cols, vals = _coo(signed=True, seed=5)
        p = als.ALSParams(rank=6, iterations=5, alpha=1.5, lambda_=0.05)
        ref = als.train(rows, cols, vals, 300, 180, p)
        staged = als.stage_dense(
            rows, cols, vals, 300, 180, p, dense_dtype="f32"
        )
        uf, itf = staged.factors(*staged.run())
        np.testing.assert_allclose(
            uf, ref.user_factors, rtol=2e-3, atol=2e-3
        )

    def test_resume_matches_straight_run(self):
        rows, cols, vals = _coo(seed=7)
        p_full = als.ALSParams(rank=6, iterations=8)
        p_half = als.ALSParams(rank=6, iterations=4)
        full = als.stage_dense(
            rows, cols, vals, 300, 180, p_full, dense_dtype="f32"
        )
        uf_full, itf_full = full.factors(*full.run())
        first = als.stage_dense(
            rows, cols, vals, 300, 180, p_half, dense_dtype="f32"
        )
        uf1, itf1 = first.factors(*first.run())
        second = als.stage_dense(
            rows, cols, vals, 300, 180, p_half,
            init_factors=(uf1, itf1), dense_dtype="f32",
        )
        uf2, itf2 = second.factors(*second.run())
        np.testing.assert_allclose(uf2, uf_full, rtol=1e-3, atol=1e-4)
        np.testing.assert_allclose(itf2, itf_full, rtol=1e-3, atol=1e-4)


class TestDenseGrid:
    def test_grid_matches_per_point_runs(self):
        import jax.numpy as jnp

        rows, cols, vals = _coo(seed=9)
        lams = [0.01, 0.1, 1.0]
        base = als.ALSParams(rank=6, iterations=4)
        staged = als.stage_dense(
            rows, cols, vals, 300, 180, base, dense_dtype="f32"
        )
        kwargs = dict(staged.static_kwargs)
        kwargs.pop("lam"), kwargs.pop("alpha")
        kwargs.pop("mesh", None)
        kwargs.pop("pallas_mode", None)
        ufs, itfs = als._train_jit_dense_grid(
            *staged.device_args[:3],
            jnp.asarray(lams, jnp.float32),
            jnp.asarray([1.0] * len(lams), jnp.float32),
            **kwargs,
        )
        for g, lam in enumerate(lams):
            p = als.ALSParams(rank=6, iterations=4, lambda_=lam)
            one = als.stage_dense(
                rows, cols, vals, 300, 180, p, dense_dtype="f32"
            )
            uf, itf = one.factors(*one.run())
            np.testing.assert_allclose(
                np.asarray(ufs[g])[:300], uf, rtol=1e-4, atol=1e-5
            )
            np.testing.assert_allclose(
                np.asarray(itfs[g])[:180], itf, rtol=1e-4, atol=1e-5
            )


class TestDenseGate:
    def test_gate_conditions(self, monkeypatch):
        rows, cols, vals = _coo(n_edges=500, seed=3)
        p = als.ALSParams(rank=8)
        ok = lambda **kw: als.dense_eligible(
            rows, cols, vals, 300, 180, p, **kw
        )
        # auto mode: below the min-edge bar → windowed keeps the wheel
        monkeypatch.delenv("PIO_DENSE_ALS", raising=False)
        assert not ok()
        # forced on: eligible at any size
        monkeypatch.setenv("PIO_DENSE_ALS", "1")
        assert ok()
        # forced off wins
        monkeypatch.setenv("PIO_DENSE_ALS", "0")
        assert not ok()
        monkeypatch.setenv("PIO_DENSE_ALS", "1")
        # single-process meshes are allowed (shard_map'd dense train);
        # multi-host is not wired for dense R staging → fall back
        import jax as _jax

        class FakeMesh:
            pass

        monkeypatch.setattr(_jax, "process_count", lambda: 2)
        assert not ok(mesh=FakeMesh())
        monkeypatch.setattr(_jax, "process_count", lambda: 1)
        # memory budget
        monkeypatch.setenv("PIO_DENSE_ALS_BYTES", "1000")
        assert not ok()
        monkeypatch.delenv("PIO_DENSE_ALS_BYTES")
        # duplicate pairs fall back (dense would merge them)
        dup_rows = np.concatenate([rows, rows[:1]])
        dup_cols = np.concatenate([cols, cols[:1]])
        dup_vals = np.concatenate([vals, vals[:1]])
        assert not als.dense_eligible(
            dup_rows, dup_cols, dup_vals, 300, 180, p
        )
        # explicit with zero-valued ratings falls back
        z_vals = vals.copy()
        z_vals[0] = 0.0
        pe = als.ALSParams(rank=8, implicit_prefs=False)
        assert not als.dense_eligible(
            rows, cols, z_vals, 300, 180, pe
        )

    def test_train_dispatches_dense_when_forced(self, monkeypatch):
        rows, cols, vals = _coo(n_edges=800, seed=4)
        called = {}
        real = als._train_dense

        def spy(*a, **kw):
            called["yes"] = True
            return real(*a, **kw)

        monkeypatch.setattr(als, "_train_dense", spy)
        monkeypatch.setenv("PIO_DENSE_ALS", "1")
        m = als.train(rows, cols, vals, 300, 180, als.ALSParams(rank=6, iterations=2))
        assert called.get("yes")
        assert m.user_factors.shape == (300, 6)
        assert np.all(np.isfinite(m.user_factors))


class TestDenseSharded:
    def test_sharded_dense_matches_single_device(self, monkeypatch):
        """The shard_map'd dense train (R row-sharded over dp, item-side
        psum combine) must train the same factors as the single-device
        dense program — the init is generated replicated and sliced, so
        agreement is near-exact in f32."""
        from predictionio_tpu.parallel.mesh import make_mesh

        monkeypatch.setenv("PIO_DENSE_ALS", "1")
        rows, cols, vals = _coo(seed=11)
        p = als.ALSParams(rank=8, iterations=5, alpha=2.0, lambda_=0.05)
        single = als.stage_dense(
            rows, cols, vals, 300, 180, p, dense_dtype="f32"
        )
        uf1, itf1 = single.factors(*single.run())
        mesh = make_mesh()
        assert mesh.devices.size > 1
        sharded = als.stage_dense(
            rows, cols, vals, 300, 180, p, dense_dtype="f32", mesh=mesh
        )
        uf2, itf2 = sharded.factors(*sharded.run())
        np.testing.assert_allclose(uf2, uf1, rtol=5e-4, atol=5e-5)
        np.testing.assert_allclose(itf2, itf1, rtol=5e-4, atol=5e-5)

    def test_train_dispatches_sharded_dense_under_mesh(self, monkeypatch):
        from predictionio_tpu.parallel.mesh import make_mesh

        monkeypatch.setenv("PIO_DENSE_ALS", "1")
        rows, cols, vals = _coo(seed=12)
        m = als.train(
            rows, cols, vals, 300, 180,
            als.ALSParams(rank=6, iterations=3), mesh=make_mesh(),
        )
        assert m.user_factors.shape == (300, 6)
        assert np.all(np.isfinite(m.user_factors))
        # matches the meshless dense train
        m1 = als.train(
            rows, cols, vals, 300, 180, als.ALSParams(rank=6, iterations=3)
        )
        c = np.corrcoef(
            m.user_factors.ravel(), m1.user_factors.ravel()
        )[0, 1]
        assert c > 0.999


class TestGroupedPairs:
    """models/als.py `_group_unique_pairs`: the job's one sort answers
    uniqueness and keeps the pairs grouped by row block for `densify`."""

    @pytest.mark.parametrize(
        "dense_dtype,scale", [("int8", 2.0), ("bf16", 1.0), ("f32", 1.0)]
    )
    def test_sorted_pairs_are_grouped_by_row_block(self, dense_dtype, scale):
        nu, ni, rows, cols, vals, _ = _build_case("an_empty_row_block")
        nup, nip = _pad_dims(nu, ni)
        kept = rows.copy(), cols.copy(), vals.copy()
        pairs = als._group_unique_pairs(
            rows, cols, vals, nup, nip, dense_dtype, scale
        )
        for now, then in zip((rows, cols, vals), kept):
            np.testing.assert_array_equal(now, then)
        per_block = np.bincount(
            rows // dense_ops.ROW_BLOCK, minlength=nup // dense_ops.ROW_BLOCK
        )
        assert per_block[1] == 0
        assert pairs.starts.dtype == pairs.offsets.dtype == np.int32
        np.testing.assert_array_equal(
            pairs.starts, np.concatenate([[0], np.cumsum(per_block)])
        )
        st = dense_ops.storage_dtype(dense_dtype)
        assert pairs.values.dtype == st
        assert pairs.grouped_for == (nup, nip, dense_dtype, scale)
        # a block's offsets ascend, and the pairs are the ones handed in
        block = np.repeat(np.arange(len(per_block)), per_block)
        cell = block.astype(np.int64) * dense_ops.ROW_BLOCK * nip + pairs.offsets
        assert np.all(np.diff(cell) > 0)
        order = np.argsort(rows.astype(np.int64) * nip + cols)
        np.testing.assert_array_equal(cell // nip, rows[order])
        np.testing.assert_array_equal(cell % nip, cols[order])
        np.testing.assert_array_equal(
            pairs.values.astype(np.float32),
            (vals[order] * scale).astype(st).astype(np.float32),
        )

    @pytest.mark.parametrize("dense_dtype", ["int8", "bf16", "f32"])
    def test_a_duplicated_pair_is_no_grouping(self, monkeypatch, dense_dtype):
        """Two pairs of one cell are neighbours after the sort whatever
        they are worth: no grouping, verdict `duplicate_pairs`, and a bare
        `stage_dense` refuses where it used to write one of the two."""
        monkeypatch.setenv("PIO_DENSE_ALS", "1")
        rows, cols, vals = _coo(seed=15)
        rows[-1], cols[-1], vals[-1] = rows[7], cols[7], vals[7] + 1.0
        nup, nip = _pad_dims(300, 180)
        assert als._group_unique_pairs(
            rows, cols, vals, nup, nip, dense_dtype, 2.0
        ) is None
        gate = als.dense_eligible(
            rows, cols, vals, 300, 180, als.ALSParams(rank=6),
            dense_dtype=dense_dtype,
        )
        assert gate.verdict == "duplicate_pairs" and gate.pairs is None
        with pytest.raises(ValueError, match="unique"):
            als.stage_dense(
                rows, cols, vals, 300, 180, als.ALSParams(rank=6),
                dense_dtype=dense_dtype,
            )

    def test_a_matrix_too_wide_for_the_block_build_is_refused(self, monkeypatch):
        monkeypatch.setenv("PIO_DENSE_ALS", "1")
        monkeypatch.setenv("PIO_DENSE_ALS_BYTES", str(1 << 40))
        rows, cols, vals = _coo(seed=16)
        n_items = dense_ops.MAX_DENSE_COLS
        gate = als.dense_eligible(
            rows, cols, vals, 300, n_items, als.ALSParams(rank=6)
        )
        assert gate.verdict == "bytes"
        with pytest.raises(ValueError, match="pair key"):
            als._group_unique_pairs(
                rows, cols, vals, 2048, n_items, "int8", 2.0
            )

    @pytest.mark.parametrize("gate_dtype,reused", [("bf16", True), ("f32", False)])
    def test_stage_dense_stages_the_same_arrays_with_a_gate_and_without(
        self, monkeypatch, gate_dtype, reused
    ):
        """What the gate's sort grouped goes on to the device as a bare
        call's own grouping would — unless it was grouped for another
        storage, and then `stage_dense` groups its own."""
        import time

        from predictionio_tpu.obs.spans import get_default_recorder

        monkeypatch.setenv("PIO_DENSE_ALS", "1")
        rows, cols, vals = _coo(seed=17)
        p = als.ALSParams(rank=6, iterations=2)
        before = time.time()
        gate = als.dense_eligible(
            rows, cols, vals, 300, 180, p, dense_dtype=gate_dtype
        )
        assert gate and gate.pairs is not None
        with monkeypatch.context() as m:
            if reused:
                m.setattr(
                    als, "_group_unique_pairs",
                    lambda *a: pytest.fail("sorted the pairs again"),
                )
            handed = als.stage_dense(rows, cols, vals, 300, 180, p, gate=gate)
        bare = als.stage_dense(rows, cols, vals, 300, 180, p)
        assert handed.static_kwargs == bare.static_kwargs
        assert handed.static_kwargs["dense_dtype"] == "int8"
        for a, b in zip(handed.device_args, bare.device_args):
            if a is not None:
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        spans = {}
        for sp in get_default_recorder().recent(before):
            spans.setdefault(sp.name, []).append(sp.attrs)
        assert spans["als.train.dense_eligible"][0]["sort_kept"] is True
        assert [
            a["pairs_grouped_reused"] for a in spans["als.stage.host_prep"]
        ] == [reused, False]
        nup, _ = _pad_dims(300, 180)
        for attrs in spans["als.stage.densify"]:
            assert attrs["blocks"] == nup // dense_ops.ROW_BLOCK
            assert attrs["pairs"] == len(rows)
            assert 0.0 < attrs["dead_slots"] < 1.0


class TestFusedDenseKernel:
    """ops/dense_pallas.py — the fused one-R-read Pallas kernel.

    Default OFF by measurement (0.70 s vs 0.60 s per ML-20M train — its
    f32 weight-derivation VPU cost exceeds the saved int8 re-read; see
    resolve_mode). Kept correct and opt-in: these interpret-mode tests
    pin equivalence with the XLA dense passes, and the full-scale TPU
    numerics were validated at ML-20M (factor corr 0.99996 vs XLA)."""

    @pytest.mark.parametrize("implicit", [True, False])
    def test_interpret_matches_xla_passes(self, implicit):
        import jax.numpy as jnp

        from predictionio_tpu.ops import dense_pallas as dp

        rng = np.random.RandomState(3)
        nr, nc, k = 512, 512, 10
        q = rng.randint(-10, 11, (nr, nc)).astype(np.int8)
        q[rng.rand(nr, nc) > 0.05] = 0
        scale, alpha = 2.0, 1.7
        r_i8 = jnp.asarray(q)
        y = rng.randn(nc, k).astype(np.float32)
        z = (y[:, :, None] * y[:, None, :]).reshape(nc, k * k)
        x = rng.randn(nr, k).astype(np.float32)
        zx = (x[:, :, None] * x[:, None, :]).reshape(nr, k * k)
        asc = jnp.asarray(
            [alpha / scale if implicit else 1.0 / scale], jnp.float32
        )
        b_ref, c_ref = dense_ops.dense_row_pass(
            r_i8, jnp.asarray(y), implicit=implicit, alpha=alpha,
            dense_dtype="int8", row_block=256, scale=scale,
        )
        b_k, c_k = dp.fused_row_pass(
            r_i8, jnp.asarray(y), jnp.asarray(z.astype(np.float32)), asc,
            implicit=implicit, interpret=True, row_tile=256, col_tile=256,
        )
        # both are bf16-operand implementations of the same f32 math;
        # they differ only in rounding order
        np.testing.assert_allclose(
            np.asarray(b_k), np.asarray(b_ref), rtol=2e-2, atol=2.0
        )
        np.testing.assert_allclose(
            np.asarray(c_k), np.asarray(c_ref), rtol=2e-2, atol=4.0
        )
        b2_ref, c2_ref = dense_ops.dense_col_pass(
            r_i8, jnp.asarray(x), implicit=implicit, alpha=alpha,
            dense_dtype="int8", row_block=256, scale=scale,
        )
        b2_k, c2_k = dp.fused_col_pass(
            r_i8, jnp.asarray(x), jnp.asarray(zx.astype(np.float32)), asc,
            implicit=implicit, interpret=True, row_tile=256, col_tile=256,
        )
        np.testing.assert_allclose(
            np.asarray(b2_k), np.asarray(b2_ref), rtol=2e-2, atol=2.0
        )
        np.testing.assert_allclose(
            np.asarray(c2_k), np.asarray(c2_ref), rtol=2e-2, atol=4.0
        )

    def test_end_to_end_interpret_train(self, monkeypatch):
        monkeypatch.setenv("PIO_PALLAS_DENSE", "interpret")
        rows, cols, vals = _coo(seed=21)
        p = als.ALSParams(rank=8, iterations=4)
        staged = als.stage_dense(rows, cols, vals, 300, 180, p)
        assert staged.static_kwargs["pallas_mode"] == "interpret"
        uf, itf = staged.factors(*staged.run())
        assert np.all(np.isfinite(uf)) and np.all(np.isfinite(itf))
        monkeypatch.setenv("PIO_PALLAS_DENSE", "0")
        ref = als.stage_dense(rows, cols, vals, 300, 180, p)
        uf_r, itf_r = ref.factors(*ref.run())
        c = np.corrcoef(uf.ravel(), uf_r.ravel())[0, 1]
        assert c > 0.999
