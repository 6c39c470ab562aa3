"""Multi-chip dry run: jit the framework's training steps over an
n-device mesh and execute one step each on tiny shapes.

This is the driver-facing proof that the multi-chip shardings compile and
execute: ALS (edge arrays over dp, factors over mp), CCO (user dim over
dp, psum-reduced co-occurrence matmul), and classification (batch over dp,
GSPMD-reduced segment-sums / gradients). What must hold under sharding is
the reference's fold semantics for partitioned aggregation
(data/.../storage/PEventAggregator.scala:85-191): per-shard partial
reductions combined associatively — here, by XLA collectives over ICI.
"""

from __future__ import annotations

from predictionio_tpu.utils.env import env_raw as _env_raw


def run_dryrun(n_devices: int) -> None:
    """Body of the dry run. Requires >= n_devices visible jax devices."""
    import jax
    import numpy as np

    from predictionio_tpu.models import als, cco, classify
    from predictionio_tpu.parallel.mesh import make_mesh

    devs = jax.devices()
    if len(devs) < n_devices:
        raise RuntimeError(
            f"dryrun needs {n_devices} devices, {len(devs)} visible "
            f"(platform={devs[0].platform if devs else 'none'})"
        )
    mesh = make_mesh(n_devices)
    rng = np.random.RandomState(0)

    with mesh:
        # --- ALS: full alternating train step, implicit + explicit ---
        n_edges, n_users, n_items = 256, 32, 24
        rows = rng.randint(0, n_users, n_edges).astype(np.int32)
        cols = rng.randint(0, n_items, n_edges).astype(np.int32)
        vals = rng.rand(n_edges).astype(np.float32) * 4.0 + 1.0
        # rank 8 → the windowed (flagship) kernel sharded part-major over
        # dp; rank 40 → the matrix-free scatter path (rank > 32). The
        # rank-8 implicit config ALSO runs with the Pallas edge kernel
        # (interpret mode on this CPU mesh) so the dryrun proves the
        # shard_map'd kernel path compiles + executes under the mesh
        # (VERDICT r4 #2 — no silent downgrade).
        import os as _os

        for implicit, rank, pallas in (
            (True, 8, False), (True, 8, True), (False, 8, False),
            (True, 40, False),
        ):
            params = als.ALSParams(
                rank=rank, iterations=1, cg_iterations=2,
                implicit_prefs=implicit,
            )
            prior = _env_raw("PIO_PALLAS_WINDOWED")
            if pallas:
                _os.environ["PIO_PALLAS_WINDOWED"] = "interpret"
            try:
                factors = als.train(
                    rows, cols, vals, n_users, n_items, params, mesh=mesh
                )
            finally:
                if pallas:
                    _os.environ.pop("PIO_PALLAS_WINDOWED", None)
                    if prior is not None:
                        _os.environ["PIO_PALLAS_WINDOWED"] = prior
            assert factors.user_factors.shape == (n_users, rank)
            assert factors.item_factors.shape == (n_items, rank)
            assert np.all(np.isfinite(factors.user_factors))
            assert np.all(np.isfinite(factors.item_factors))

        # the DEFAULT ML-20M path: shard_map'd dense-W train (R row-
        # sharded over dp, item-side psum) — dedupe pairs first (the
        # dense gate requires one rating per cell)
        keys = np.unique(
            rows.astype(np.int64) * n_items + cols.astype(np.int64)
        )
        d_rows = (keys // n_items).astype(np.int32)
        d_cols = (keys % n_items).astype(np.int32)
        d_vals = np.float32(1.0) + (keys % 5).astype(np.float32)
        prior = _env_raw("PIO_DENSE_ALS")
        _os.environ["PIO_DENSE_ALS"] = "1"
        try:
            factors = als.train(
                d_rows, d_cols, d_vals, n_users, n_items,
                als.ALSParams(rank=8, iterations=1, cg_iterations=2),
                mesh=mesh,
            )
        finally:
            _os.environ.pop("PIO_DENSE_ALS", None)
            if prior is not None:
                _os.environ["PIO_DENSE_ALS"] = prior
        assert factors.user_factors.shape == (n_users, 8)
        assert np.all(np.isfinite(factors.user_factors))
        assert np.all(np.isfinite(factors.item_factors))

        # --- Fleet (ISSUE 10): model-axis sharded dense train — R 2-D
        # block-sharded over (dp, mp), item factors row-sharded over mp;
        # must agree with the single-device dense solve (the contract
        # tests/test_fleet_sharded.py enforces at full tolerance)
        if n_devices >= 2:
            from predictionio_tpu.parallel.mesh import MeshConf

            p2 = als.ALSParams(rank=8, iterations=1, cg_iterations=2)
            ref = als.stage_dense(
                d_rows, d_cols, d_vals, n_users, n_items, p2,
                dense_dtype="f32",
            )
            uf_ref, itf_ref = ref.factors(*ref.run())
            # odd counts (say 5) round down to the largest dp×2 grid
            mesh2 = MeshConf(
                dp=n_devices // 2, mp=2, devices=2 * (n_devices // 2)
            ).build()
            st = als.stage_dense(
                d_rows, d_cols, d_vals, n_users, n_items, p2,
                dense_dtype="f32", mesh=mesh2,
            )
            uf2, itf2 = st.factors(*st.run())
            np.testing.assert_allclose(uf2, uf_ref, rtol=1e-3, atol=1e-4)
            np.testing.assert_allclose(itf2, itf_ref, rtol=1e-3, atol=1e-4)

            # --- Fleet: sharded serving — row-sharded factor state,
            # local top-k per shard + global merge == dense top-k
            from predictionio_tpu.fleet import ShardedRuntime

            srt = ShardedRuntime.from_factors(factors)
            q = np.arange(min(4, n_users))
            v_d, i_d = als.recommend(factors, q, 5)
            v_s, i_s = srt.recommend(q, 5)
            np.testing.assert_allclose(v_s, v_d, rtol=1e-4, atol=1e-5)
            assert (i_s == i_d).all()

        # --- CCO: user-sharded co-occurrence + LLR top-n ---
        n_u, n_i, n_j = 40, 16, 12
        primary = (rng.rand(n_u, n_i) < 0.2).astype(np.float32)
        secondary = (rng.rand(n_u, n_j) < 0.2).astype(np.float32)
        scores, idx = cco.cross_occurrence_topn(
            primary, secondary, top_n=5, mesh=mesh
        )
        assert scores.shape == (n_i, 5) and idx.shape == (n_i, 5)
        assert np.all(np.isfinite(scores))

        # --- Classification: batch-sharded NB segment-sums + LR gradient ---
        n, d, c = 200, 6, 3
        x = rng.rand(n, d).astype(np.float32)
        y = rng.randint(0, c, n).astype(np.int32)
        nb = classify.train_naive_bayes(x, y, c, mesh=mesh)
        assert nb.log_likelihood.shape == (c, d)
        assert np.all(np.isfinite(nb.log_likelihood))
        lr = classify.train_logistic_regression(
            x, y, c, iterations=5, mesh=mesh
        )
        assert lr.weights.shape == (d + 1, c)
        assert np.all(np.isfinite(lr.weights))


# Child-process bootstrap: pin the CPU platform (utils/cpuonly.py), then
# run the body.
_CHILD_TEMPLATE = """\
from predictionio_tpu.utils.cpuonly import force_cpu_platform
force_cpu_platform()  # device count comes from the parent's XLA_FLAGS
from predictionio_tpu.parallel.dryrun import run_dryrun
run_dryrun({n})
print("DRYRUN_OK")
"""


def run_dryrun_subprocess(n_devices: int, timeout: float = 900.0) -> None:
    """Self-provisioning path: spawn a fresh interpreter with an n-device
    virtual CPU platform forced via XLA_FLAGS, regardless of what device
    the calling process is bound to."""
    import os
    import subprocess
    import sys

    repo_root = os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    from predictionio_tpu.utils.cpuonly import force_cpu_env

    env = force_cpu_env(dict(os.environ), n_devices)
    env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")

    proc = subprocess.run(
        [sys.executable, "-c", _CHILD_TEMPLATE.format(n=n_devices)],
        env=env,
        cwd=repo_root,
        capture_output=True,
        text=True,
        timeout=timeout,
    )
    if proc.returncode != 0 or "DRYRUN_OK" not in proc.stdout:
        raise RuntimeError(
            f"multichip dryrun subprocess failed (rc={proc.returncode})\n"
            f"--- stdout ---\n{proc.stdout[-4000:]}\n"
            f"--- stderr ---\n{proc.stderr[-4000:]}"
        )
