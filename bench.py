"""Headline benchmark: implicit-ALS training at MovieLens-20M scale plus
device-level AND framework-level serving, in one JSON line.

Workload (BASELINE.json north star): the scala-parallel-recommendation
template's MLlib ALS at its quickstart hyperparameters (rank 10,
20 iterations, lambda 0.01 — examples/scala-parallel-recommendation/*/
engine.json), scaled to the MovieLens-20M shape: 20,000,263 events over
138,493 users x 26,744 items (synthetic zipf-like popularity so the
degree distribution resembles the real corpus).

Measurement discipline (VERDICT r2 #1):
- The headline `value` is DEVICE throughput: the staged training program
  (all edge data resident in HBM) timed over N_RUNS full trains with the
  first run discarded; min/mean/std reported. Host prep (plan + sort) and
  host->device transfer are reported separately.
- Each timed run ends in a scalar-reduce fetch of the factors, so the
  device has finished (and the result is finite) before the clock stops.
- `e2e_train_sec` times one full framework train (als.train: host prep +
  transfer + device) for the end-to-end number.

Roofline (VERDICT r2 #1b): bytes_model_gb is the padded-intermediate
traffic model of the windowed one-hot pass (see ops/windowed.py): per
padded edge, one 512 B factor-row gather + payload write/read + one-hot
write/read (each 512 B lane-padded) + 16 B of indices/weights, plus
per-block partial write/read. hbm_gbps = model bytes / device time,
reported against the HBM roof of the device it ran on — the
obs/devprof.py peak-table row for `device_kind`, or the
PIO_BENCH_HBM_PEAK / PIO_BENCH_PEAK_FLOPS overrides; a device with
neither has no roofline and the train benchmark refuses to run.
algorithmic_min_gb is the useful-bytes floor (40 B factor row + 16 B
edge data). MFU stays the honest analytic-FLOPs number: this workload is
memory-bound and MFU is expected to be tiny; hbm_gbps is the utilization
metric that matters.

Serving (VERDICT r2 #2): device-level single-dispatch latency/qps as
before, PLUS the real product path — a QueryServer (HTTP + JSON extract +
micro-batch dispatcher + serve) over a trained recommendation engine on
the full 26,744-item catalog, hammered by concurrent clients:
serving_framework_qps / p50 / p99.

Device profiling (ISSUE 3): MFU/roofline numbers now ALSO come from the
framework's own obs/devprof registry (XLA cost_analysis per executable ×
measured device seconds). The hand-derived models above stay as the
cross-check: serving_mfu_framework vs serving_mfu_hand must agree within
2×, and train_devprof reports the registry's view of the headline train
executable next to the analytic mfu.

Set PIO_BENCH_SCALE=small for a quick CI-sized run (100K shape).
"""

from __future__ import annotations

import json
import os
from predictionio_tpu.utils.env import env_opt_float, env_raw, env_str
import time

import numpy as np

SMALL = env_str("PIO_BENCH_SCALE") == "small"

if SMALL:
    N_EVENTS, N_USERS, N_ITEMS = 100_000, 943, 1682
else:
    N_EVENTS, N_USERS, N_ITEMS = 20_000_263, 138_493, 26_744

RANK = 10
ITERATIONS = 20
LAMBDA = 0.01
ALPHA = 1.0
N_RUNS = 6  # timed device runs; the first is discarded
BASELINE_SAMPLE_EVENTS = 1_000_000  # CPU baseline subsample (extrapolated)


def device_peaks() -> tuple[float, float]:
    """(HBM bytes/s, FLOP/s) roofs of the device this process runs on:
    the env override, else the devprof table row for its device_kind.
    A device with neither is an error, not a default."""
    from predictionio_tpu.obs import devprof

    import jax  # noqa: F401 — platform_info reports only a loaded jax

    info = devprof.platform_info()
    hbm = env_opt_float("PIO_BENCH_HBM_PEAK") or info["peak_hbm_bps"]
    flops = env_opt_float("PIO_BENCH_PEAK_FLOPS") or info["peak_flops"]
    if not hbm or not flops:
        raise RuntimeError(
            f"no roofline peaks for device_kind {info['device_kind']!r} "
            f"(platform {info['platform']!r}): add its row to "
            "obs/devprof.py PEAK_TABLE or set PIO_BENCH_HBM_PEAK / "
            "PIO_BENCH_PEAK_FLOPS"
        )
    return hbm, flops


def device_summary() -> dict:
    """The device every emitted number belongs to, as jax reports it."""
    from predictionio_tpu.obs import devprof

    import jax  # noqa: F401 — platform_info reports only a loaded jax

    info = devprof.platform_info()
    return {
        "platform": info["platform"],
        "kind": info["device_kind"],
        "count": info["device_count"],
    }


def make_data(seed: int = 0):
    """zipf-ish popularity so degree distribution resembles MovieLens.

    Round 5 on: pairs are UNIQUE (draw-with-replacement batches deduped
    until N_EVENTS distinct (user, item) cells) — real MovieLens ratings
    are one-per-pair, and the dense-W fast path requires it. The r4
    workload had ~4.6% duplicate pairs; every path is re-measured on the
    new workload in the same run, so within-round A/Bs stay apples-to-
    apples (r3/r4 ledger numbers are on the old draw)."""
    rng = np.random.RandomState(seed)
    user_p = rng.dirichlet(np.full(N_USERS, 0.3))
    item_p = rng.dirichlet(np.full(N_ITEMS, 0.3))
    keys = np.zeros(0, np.int64)
    while keys.size < N_EVENTS:
        draw = int((N_EVENTS - keys.size) * 1.15) + 1000
        r = rng.choice(N_USERS, draw, p=user_p).astype(np.int32)
        c = rng.choice(N_ITEMS, draw, p=item_p).astype(np.int32)
        keys = np.unique(
            np.concatenate([keys, r.astype(np.int64) * N_ITEMS + c])
        )
    rng.shuffle(keys)
    keys = keys[:N_EVENTS]
    rows = (keys // N_ITEMS).astype(np.int32)
    cols = (keys % N_ITEMS).astype(np.int32)
    vals = rng.randint(1, 6, N_EVENTS).astype(np.float32)
    return rows, cols, vals


def als_train_flops(n_edges: int, n_users: int, n_items: int) -> float:
    """Analytic useful FLOPs of one full train on the windowed gram path:
    per half-step, one edge pass builds b (3EK) and the K^2 gram
    corrections (3EK^2), fixed gram 2NK^2, then cg dense matvecs
    (2NK^2 + ~8NK each). One-hot matmul FLOPs are real device work but
    not algorithmically useful, so they are excluded — MFU here is the
    honest 'useful flops' number."""
    k, cg = RANK, 3
    e = n_edges

    def half(n):
        return (
            2 * n * k * k + 3 * e * k * k + 3 * e * k
            + cg * (2 * n * k * k + 8 * n * k)
        )

    return ITERATIONS * (half(n_users) + half(n_items))


def windowed_bytes_model(staged, pallas: bool) -> tuple[float, float]:
    """(model_bytes, algorithmic_min_bytes) for ONE full train.

    XLA scan path, per padded edge and per half-step: 512 B gather read
    (K=10 f32 row lane-padded to 128) + 2x512 B payload write/read +
    2x512 B one-hot write/read + 16 B indices/weights; plus per-block
    (S*D lanes) partial write/read and the CG matvec traffic (cg+1 reads
    of the flat (N,K^2) operators).

    Pallas path (ops/windowed_pallas.py): the one-hot and the
    outer-product payload never leave VMEM; HBM sees the per-chunk
    transposed gather (K->16 sublane-padded: 64 B/slot write + read),
    the weights/local/src streams, the per-block (S, K+K^2) partials
    (write + read by the segment-sum, as on the XLA path), and the same
    CG sweeps — the one-hot and payload terms (~39 GB/pass at ML-20M)
    are the traffic the kernel eliminates."""
    k = RANK
    d = k + k * k
    row_bytes = 128 * 4  # lane-padded f32 row
    e_p_user = staged.device_args[0].size  # padded edges, user plan
    e_p_item = staged.device_args[5].size
    n_blocks = staged.device_args[4].size + staged.device_args[9].size
    n_pad_rows = staged.device_args[10].size + staged.device_args[11].size
    cg_ops = (3 + 1) * n_pad_rows * (k * k) * 4  # flat operator sweeps
    partials = 2 * n_blocks * 128 * d * 4  # write + read of partials
    if pallas:
        # y_t (K->16 sublanes, B_E lanes) write by gather + read by kernel
        per_edge = 2 * 16 * 4 + 16 + 8 + 4 + 40
        per_iter = (
            (e_p_user + e_p_item) * per_edge + partials + cg_ops
        )
    else:
        per_edge = 5 * row_bytes + 16
        per_iter = (e_p_user + e_p_item) * per_edge + partials + cg_ops
    min_per_iter = (e_p_user + e_p_item) * (40 + 16) + n_pad_rows * d * 4
    return ITERATIONS * per_iter, ITERATIONS * min_per_iter


def dense_models(n_u_p: int, n_i_p: int, dense_dtype: str) -> tuple[float, float]:
    """(model_bytes, executed_mxu_flops) for ONE dense-path train.

    HBM model ASSUMES XLA fuses the weight-tile derivations into the
    matmul reads (measurement confirmed it does: an unfused model with
    write+read of both derived tiles predicted 1.36 TB/train, >2x the
    HBM roof for the observed 0.6 s — physically impossible, so the
    tiles never hit HBM). Fused: each half-step reads R twice (once per
    weight-tile matmul, deriving tiles in registers) + the CG
    flat-operator sweeps. Executed MXU flops: two
    (rows x cols x 128-lane) matmuls per half-step (K=10 and K^2=100
    both occupy one 128-lane MXU tile)."""
    from predictionio_tpu.ops.dense import BYTES_PER_CELL

    r_bytes = n_u_p * n_i_p * BYTES_PER_CELL.get(dense_dtype, 2)
    cg_ops = (3 + 1) * (n_u_p + n_i_p) * (RANK * RANK) * 4
    per_iter = 2 * (2 * r_bytes) + 2 * cg_ops
    flops_per_pass = 2 * 2 * n_u_p * n_i_p * 128
    return ITERATIONS * per_iter, ITERATIONS * 2 * flops_per_pass


def bench_tpu(rows, cols, vals):
    """Device/e2e throughput stats + roofline for the staged train.

    Measures the dense-W fast path (the default at this scale — the
    below-1%-density reformulation, ops/dense.py) AND both windowed
    edge-pass implementations (Pallas kernel + XLA scan path) for the
    A/B ledger. The headline is whatever als.train would actually run,
    which at ML-20M is the dense path."""
    import jax
    import jax.numpy as jnp

    from predictionio_tpu.models import als

    HBM_PEAK, FLOP_PEAK = device_peaks()
    params = als.ALSParams(
        rank=RANK, iterations=ITERATIONS, lambda_=LAMBDA, alpha=ALPHA,
        implicit_prefs=True,
    )
    fetch = jax.jit(lambda u, i: jnp.sum(u) + jnp.sum(i))

    def sync(uf, itf):
        s = float(np.asarray(fetch(uf, itf)))
        # a non-finite factor sum means the train diverged or a kernel
        # miscompiled — never let a garbage train post a headline number
        # (round 3 did exactly that: an XLA fori-loop miscompile NaN'd
        # the factors and the throughput still "measured" fine)
        assert np.isfinite(s), "training produced non-finite factors"
        return s

    def measure(mode):
        if mode is None:  # honor the caller's own PIO_PALLAS_WINDOWED
            os.environ.pop("PIO_PALLAS_WINDOWED", None)
            if _prior_mode is not None:
                os.environ["PIO_PALLAS_WINDOWED"] = _prior_mode
        else:
            os.environ["PIO_PALLAS_WINDOWED"] = mode
        staged = als.stage_windowed(
            rows, cols, vals, N_USERS, N_ITEMS, params
        )
        t0 = time.perf_counter()
        sync(*staged.run())  # compile + warmup
        compile_sec = time.perf_counter() - t0
        runs = []
        for _ in range(N_RUNS):
            t0 = time.perf_counter()
            uf_w, itf_w = staged.run()
            sync(uf_w, itf_w)
            runs.append(time.perf_counter() - t0)
        runs = runs[1:]  # discard the first timed run
        best = min(runs)
        pallas = staged.static_kwargs["pallas_mode"] is not None
        model_bytes, min_bytes = windowed_bytes_model(staged, pallas)
        return staged, {
            "_factors_device": (uf_w, itf_w),
            "runs_sec": runs,
            "throughput": [N_EVENTS * ITERATIONS / r for r in runs],
            "device_best_sec": best,
            "compile_sec": compile_sec,
            "pallas": pallas,
            "mfu": als_train_flops(N_EVENTS, N_USERS, N_ITEMS)
            / best / FLOP_PEAK,
            "hbm_gbps": model_bytes / best / 1e9,
            "hbm_pct_of_roof": model_bytes / best / HBM_PEAK,
            "bytes_model_gb": model_bytes / 1e9,
            "algorithmic_min_gb": min_bytes / 1e9,
        }

    # dense path FIRST (fresh HBM), released before the windowed arrays
    # stage
    dense = None
    if als.dense_eligible(rows, cols, vals, N_USERS, N_ITEMS, params):
        staged_d = als.stage_dense(rows, cols, vals, N_USERS, N_ITEMS, params)
        t0 = time.perf_counter()
        sync(*staged_d.run())  # compile + warmup
        d_compile = time.perf_counter() - t0
        d_runs = []
        for _ in range(N_RUNS):
            t0 = time.perf_counter()
            uf_d, itf_d = staged_d.run()
            sync(uf_d, itf_d)
            d_runs.append(time.perf_counter() - t0)
        d_runs = d_runs[1:]
        best_d = min(d_runs)
        d_dtype = staged_d.static_kwargs["dense_dtype"]
        n_u_p, n_i_p = staged_d.device_args[0].shape
        model_bytes, mxu_flops = dense_models(n_u_p, n_i_p, d_dtype)
        dense = {
            "runs_sec": d_runs,
            "throughput": [N_EVENTS * ITERATIONS / r for r in d_runs],
            "device_best_sec": best_d,
            "compile_sec": d_compile,
            "dtype": d_dtype,
            "host_prep_sec": staged_d.host_prep_sec,
            "transfer_sec": staged_d.transfer_sec,
            "hbm_gbps": model_bytes / best_d / 1e9,
            "hbm_pct_of_roof": model_bytes / best_d / HBM_PEAK,
            "bytes_model_gb": model_bytes / 1e9,
            "mxu_util_executed": mxu_flops / best_d / FLOP_PEAK,
            "mfu": als_train_flops(N_EVENTS, N_USERS, N_ITEMS)
            / best_d / FLOP_PEAK,
            "factors": staged_d.factors(uf_d, itf_d),
        }
        del staged_d, uf_d, itf_d

    _prior_mode = env_raw("PIO_PALLAS_WINDOWED")
    staged, main = measure(None)  # default: pallas on TPU, XLA elsewhere
    _, xla = measure("0")
    xla.pop("_factors_device", None)
    # restore the caller's setting for the e2e train below
    os.environ.pop("PIO_PALLAS_WINDOWED", None)
    if _prior_mode is not None:
        os.environ["PIO_PALLAS_WINDOWED"] = _prior_mode

    # one end-to-end framework train (host prep + transfer + device)
    t0 = time.perf_counter()
    als.train(rows, cols, vals, N_USERS, N_ITEMS, params)
    e2e_sec = time.perf_counter() - t0

    main.update(
        host_prep_sec=staged.host_prep_sec,
        transfer_sec=staged.transfer_sec,
        e2e_sec=e2e_sec,
        xla_path=xla,
        pallas_speedup=(
            xla["device_best_sec"] / main["device_best_sec"]
            if main["pallas"] else 1.0
        ),
    )
    if dense is not None:
        # cross-check the two implementations at FULL scale (the r4
        # miscompile lesson: only full-scale disagreement catches TPU
        # codegen bugs) — near-1 correlation, and both finite by sync()
        uf_w, itf_w = staged.factors(*main.pop("_factors_device"))
        uf_d, itf_d = dense.pop("factors")
        dense["factor_corr_users"] = float(
            np.corrcoef(uf_d.ravel(), uf_w.ravel())[0, 1]
        )
        dense["factor_corr_items"] = float(
            np.corrcoef(itf_d.ravel(), itf_w.ravel())[0, 1]
        )
        # assert BOTH sides: row pass and col pass are independently
        # compiled programs — a col-pass miscompile would corrupt item
        # factors while user factors stay correlated
        assert dense["factor_corr_users"] > 0.99, (
            "dense/windowed USER factor divergence at full scale"
        )
        assert dense["factor_corr_items"] > 0.99, (
            "dense/windowed ITEM factor divergence at full scale"
        )
        dense["speedup_vs_windowed"] = (
            main["device_best_sec"] / dense["device_best_sec"]
        )
    main["dense"] = dense
    # framework-derived train roofline (ISSUE 3): the devprof registry's
    # view of the headline executable — accumulated over warmup + timed
    # runs, so mean-shaped where the hand numbers use best-of; the two
    # are reported side by side, not reconciled
    from predictionio_tpu.obs import devprof

    # the dense path dispatches als.train_dense_sharded under a mesh —
    # try both so multi-chip runs don't silently lose the block
    candidates = (
        ("als.train_dense", "als.train_dense_sharded")
        if dense is not None else ("als.train_windowed",)
    )
    prof_name = prof = None
    for prof_name in candidates:
        prof = devprof.get_profiler().executable(prof_name)
        if prof is not None:
            break
    if prof is not None:
        main["devprof_train"] = {
            "executable": prof_name,
            "mfu_framework": prof.get("mfu"),
            "hbm_fraction_framework": prof.get("hbm_fraction_of_roof"),
            "device_seconds": round(prof["device_seconds"], 3),
            "compile_seconds": prof["compile_seconds"],
            "invocations": prof["invocations"],
        }
    return main


def bench_numpy_baseline(rows, cols, vals, sample_iters: int = 3):
    """Reference-style single-process CPU ALS: per-row k x k normal
    equations solved one row at a time (the shape of MLlib's local-mode
    compute), reported as events/sec with per-iteration variance.

    Subsamples by USER (keeping every kept user's full event list) so the
    events-per-row density — which sets how per-row fixed costs amortize —
    matches the full workload; subsampling events directly would starve
    rows and unfairly slow the baseline."""
    if len(rows) > BASELINE_SAMPLE_EVENTS:
        frac = BASELINE_SAMPLE_EVENTS / len(rows)
        keep_users = int(N_USERS * frac)
        sel = rows < keep_users
        rows, cols, vals = rows[sel], cols[sel], vals[sel]
    n = len(rows)
    n_users = int(rows.max()) + 1
    n_items = int(cols.max()) + 1
    rng = np.random.RandomState(3)
    uf = rng.standard_normal((n_users, RANK)).astype(np.float32) / np.sqrt(RANK)
    itf = rng.standard_normal((n_items, RANK)).astype(np.float32) / np.sqrt(RANK)
    conf = 1.0 + ALPHA * np.abs(vals)

    def half_step(fixed, src, dst, c, n_dst):
        gram = fixed.T @ fixed + LAMBDA * np.eye(RANK, dtype=np.float32)
        out = np.empty((n_dst, RANK), dtype=np.float32)
        order = np.argsort(dst, kind="stable")
        ds, ss, cs = dst[order], src[order], c[order]
        bounds = np.searchsorted(ds, np.arange(n_dst + 1))
        for d in range(n_dst):
            lo, hi = bounds[d], bounds[d + 1]
            y = fixed[ss[lo:hi]]
            cw = cs[lo:hi]
            a = gram + y.T @ ((cw - 1.0)[:, None] * y)
            b = y.T @ cw
            out[d] = np.linalg.solve(a, b)
        return out

    iter_rates = []
    for _ in range(sample_iters):
        t0 = time.perf_counter()
        uf = half_step(itf, cols, rows, conf, n_users)
        itf = half_step(uf, rows, cols, conf, n_items)
        iter_rates.append(n / (time.perf_counter() - t0))
    return {
        "events_per_sec": float(np.mean(iter_rates)),
        "std": float(np.std(iter_rates)),
        "sample_events": n,
        "iters": sample_iters,
    }


def bench_grid_tuning():
    """4-point λ-grid vs 4 sequential trains at 1M edges (VERDICT r3 #6:
    the grid shares one staged WindowPlan and trains as one batched
    device program; done-bar ≥2x)."""
    from predictionio_tpu.models import als

    rng = np.random.RandomState(5)
    nu, ni, ne = (10_000, 3_000, 1_000_000) if not SMALL else (943, 1682, 100_000)
    rows = rng.randint(0, nu, ne).astype(np.int32)
    cols = rng.randint(0, ni, ne).astype(np.int32)
    vals = rng.randint(1, 6, ne).astype(np.float32)
    params_list = [
        als.ALSParams(rank=RANK, iterations=10, lambda_=lam)
        for lam in (0.003, 0.01, 0.1, 1.0)
    ]
    als.train_grid(rows, cols, vals, nu, ni, params_list)  # warm
    als.train(rows, cols, vals, nu, ni, params_list[0])  # warm
    t0 = time.perf_counter()
    als.train_grid(rows, cols, vals, nu, ni, params_list)
    t_grid = time.perf_counter() - t0
    t0 = time.perf_counter()
    for p in params_list:
        als.train(rows, cols, vals, nu, ni, p)
    t_seq = time.perf_counter() - t0

    # rank-axis grid (VERDICT r4 #7): 2 ranks x 2 lambdas — per-rank
    # batched launches over ONE shared staging vs 4 serial trains
    rank_list = [
        als.ALSParams(rank=r, iterations=10, lambda_=lam)
        for r in (RANK, RANK + 6)
        for lam in (0.01, 0.1)
    ]
    als.train_grid(rows, cols, vals, nu, ni, rank_list)  # warm
    for p in (rank_list[0], rank_list[2]):  # warm both rank shapes
        als.train(rows, cols, vals, nu, ni, p)
    t0 = time.perf_counter()
    als.train_grid(rows, cols, vals, nu, ni, rank_list)
    t_rgrid = time.perf_counter() - t0
    t0 = time.perf_counter()
    for p in rank_list:
        als.train(rows, cols, vals, nu, ni, p)
    t_rseq = time.perf_counter() - t0
    return {
        "grid_sec": t_grid, "seq_sec": t_seq, "speedup": t_seq / t_grid,
        "rank_grid_sec": t_rgrid, "rank_seq_sec": t_rseq,
        "rank_grid_speedup": t_rseq / t_rgrid,
    }


def bench_serving_device():
    """Device-level floor: warmed recommend latency (batch 1) and
    micro-batched dispatch qps (batch 64) over the full item catalog —
    one jit dispatch + result fetch, no HTTP/extract/serve overhead."""
    import jax

    from predictionio_tpu.ops.topk import masked_top_k

    rng = np.random.RandomState(7)
    itf = jax.device_put(
        rng.standard_normal((N_ITEMS, RANK)).astype(np.float32)
    )

    @jax.jit
    def recommend(u):
        return masked_top_k(u @ itf.T, 10, None)

    def run(batch):
        u = rng.standard_normal((batch, RANK)).astype(np.float32)
        vals, idx = recommend(u)  # warm this batch shape
        np.asarray(idx)
        times = []
        for _ in range(15):
            t0 = time.perf_counter()
            _, idx = recommend(u)
            np.asarray(idx)  # force fetch — end-to-end incl. transfer
            times.append(time.perf_counter() - t0)
        return float(np.median(times))

    p50_single = run(1)
    batch = 64
    per_batch = run(batch)
    return p50_single * 1e3, batch / per_batch


def bench_serving_kernels():
    """ISSUE 11: the staged-serving device floor across dtypes/kernels.

    Measures warmed recommend latency (batch 1) and batched qps
    (batch 64) through `als.recommend_serving` — the path the engine
    actually serves — for f32 and int8 staged states, reports which
    kernel mode resolved (the fused Pallas kernel on TPU, the XLA
    two-step elsewhere), and the int8-vs-f32 score agreement on the
    bench shapes."""
    from predictionio_tpu.data.store.bimap import BiMap
    from predictionio_tpu.models import als

    rng = np.random.RandomState(7)
    n_users_local = min(N_USERS, 65_536)
    f = als.ALSFactors(
        user_factors=rng.standard_normal(
            (n_users_local, RANK)
        ).astype(np.float32),
        item_factors=rng.standard_normal(
            (N_ITEMS, RANK)
        ).astype(np.float32),
        user_vocab=BiMap({}),
        item_vocab=BiMap({}),
    )

    def measure(sv, batch):
        rows = rng.randint(0, n_users_local, batch).astype(np.int32)
        als.recommend_serving(sv, rows, 10)  # warm this shape
        times = []
        for _ in range(15):
            t0 = time.perf_counter()
            als.recommend_serving(sv, rows, 10)
            times.append(time.perf_counter() - t0)
        return float(np.median(times))

    def measure_similar(sv, batch):
        rows = rng.randint(0, N_ITEMS, batch).astype(np.int32)
        als.similar_serving(sv, rows, 10)  # warm this shape
        times = []
        for _ in range(15):
            t0 = time.perf_counter()
            als.similar_serving(sv, rows, 10)
            times.append(time.perf_counter() - t0)
        return float(np.median(times))

    out = {}
    for dt in ("f32", "bf16", "int8"):
        sv = als.stage_serving(f, serve_dtype=dt)
        p50 = measure(sv, 1)
        per_batch = measure(sv, 64)
        out[dt] = {
            "p50_ms": p50 * 1e3,
            "qps": 64 / per_batch,
            "resident_mb": sv.device_nbytes() / 1e6,
            "mode": sv.mode or "xla",
            # ISSUE 14: fused `similar` serves off the SAME staged slab
            "similar_p50_ms": measure_similar(sv, 8) * 1e3,
        }
    # int8-vs-f32 score agreement on a (64, I) slab
    from predictionio_tpu.ops.recommend_pallas import (
        pad_items,
        pack_mask_np,
        quantize_rows_np,
    )

    sample = rng.randint(0, n_users_local, 64)
    uq, us = quantize_rows_np(f.user_factors[sample])
    iq, isc = quantize_rows_np(f.item_factors)
    s_f32 = f.user_factors[sample] @ f.item_factors.T
    s_int8 = (
        uq.astype(np.int32) @ iq.T.astype(np.int32)
    ).astype(np.float32) * us[:, None] * isc[None, :]
    out["int8_rel_err"] = float(
        np.max(np.abs(s_int8 - s_f32)) / np.abs(s_f32).max()
    )
    # bit-packed exclusion mask traffic vs the old f32 0/1 input
    i_p = pad_items(N_ITEMS)
    mask = rng.rand(64, N_ITEMS) < 0.3
    out["mask_packed_bytes_ratio"] = (
        64 * i_p * 4 / pack_mask_np(mask, i_p).nbytes
    )
    # ISSUE 14: the fused CCO/universal batch_score_topk tail
    from predictionio_tpu.models import cco
    from predictionio_tpu.ops.recommend_pallas import resolve_mode

    n_corr = 50
    tables = [(
        rng.randint(-1, 2000, (N_ITEMS, n_corr)).astype(np.int32),
        np.abs(rng.standard_normal((N_ITEMS, n_corr))).astype(np.float32),
        2000,
    )]
    hists = [rng.randint(-1, 2000, (64, 64)).astype(np.int32)]
    ex = np.full((64, 128), -1, np.int32)
    cco.batch_score_topk(tables, hists, ex, 64)  # warm
    times = []
    for _ in range(10):
        t0 = time.perf_counter()
        cco.batch_score_topk(tables, hists, ex, 64)
        times.append(time.perf_counter() - t0)
    out["cco_p50_ms"] = float(np.median(times)) * 1e3
    out["cco_mode"] = resolve_mode("auto") or "xla"
    # ISSUE 14: sharded tier dtype staging + dirty-row publish — in THIS
    # process, on the devices it can see (one process per chip: a child
    # would need the chip this parent holds). One visible device means
    # there is no sharded tier to measure, and the keys are not emitted.
    import jax

    out["sharded"] = None
    if len(jax.devices()) >= 2:
        from predictionio_tpu.fleet.runtime import ShardedRuntime

        n_u_sh, n_i_sh = min(n_users_local, 8192), min(N_ITEMS, 16_384)
        uf = rng.standard_normal((n_u_sh, RANK)).astype(np.float32)
        itf = rng.standard_normal((n_i_sh, RANK)).astype(np.float32)
        r32 = ShardedRuntime(uf, itf, serve_dtype="f32")
        r8 = ShardedRuntime(uf, itf, serve_dtype="int8")
        r8.recommend(np.arange(8), 10)  # warm
        times = []
        for _ in range(10):
            t0 = time.perf_counter()
            r8.recommend(np.arange(8), 10)
            times.append(time.perf_counter() - t0)
        dirty = rng.standard_normal((16, RANK)).astype(np.float32)
        t0 = time.perf_counter()
        r8.update_user_rows(np.arange(16), dirty)
        publish_ms = (time.perf_counter() - t0) * 1e3
        out["sharded"] = {
            "int8_resident_mb_per_shard":
                r8.device_bytes()["per_shard"] / 1e6,
            "int8_over_f32_resident":
                r8.device_bytes()["per_shard"]
                / r32.device_bytes()["per_shard"],
            "int8_p50_ms": float(np.median(times)) * 1e3,
            "publish_dirty16_ms": publish_ms,
            "shards": r8.n_shards,
        }
    return out


def bench_batching_ab():
    """ISSUE 11: continuous vs windowed micro-batching p99 under the
    SAME closed-loop load on the same trained engine — the acceptance
    check that admitting arrivals into in-flight buckets does not
    regress tail latency vs fixed windows."""
    from predictionio_tpu.data.event import Event
    from predictionio_tpu.data.storage.base import App
    from predictionio_tpu.data.storage.registry import (
        SourceConfig,
        Storage,
        StorageConfig,
    )
    from predictionio_tpu.workflow.core import run_train
    from predictionio_tpu.workflow.server import (
        QueryServer,
        QueryServerConfig,
        latest_completed_runtime,
    )

    cfg = StorageConfig(
        sources={"MEM": SourceConfig("MEM", "memory", {})},
        repositories={
            "METADATA": "MEM", "EVENTDATA": "MEM", "MODELDATA": "MEM",
        },
    )
    storage = Storage(cfg)
    apps = storage.get_meta_data_apps()
    app_id = apps.insert(App(0, "abapp"))
    storage.get_events().init_app(app_id)
    rng = np.random.RandomState(23)
    n_users_ab, n_items_ab = 400, 4000
    batch = [
        Event(
            event="rate", entity_type="user",
            entity_id=f"u{int(rng.randint(n_users_ab))}",
            target_entity_type="item", target_entity_id=f"i{i}",
            properties={"rating": float(rng.randint(1, 6))},
        )
        for i in range(n_items_ab)
    ]
    storage.get_events().insert_batch(batch, app_id)
    variant = {
        "id": "abrec",
        "engineFactory":
            "predictionio_tpu.engines.recommendation.RecommendationEngine",
        "datasource": {"params": {"app_name": "abapp"}},
        "algorithms": [
            {"name": "als", "params": {"rank": RANK, "num_iterations": 3}}
        ],
    }
    run_train(storage, variant)
    runtime = latest_completed_runtime(storage, "abrec", "0", "abrec")
    make_body = lambda i: json.dumps(  # noqa: E731
        {"user": f"u{i % n_users_ab}", "num": 10}
    ).encode()
    servers = {}
    out = {}
    try:
        for mode in ("continuous", "windowed"):
            srv = QueryServer(
                storage, runtime,
                QueryServerConfig(ip="127.0.0.1", port=0, batching=mode),
            )
            servers[mode] = (srv, srv.start())
        for mode, (_, port) in servers.items():
            # warm: bucket-shape compiles + TCP stacks settle
            _hammer_query_server(port, make_body, n_clients=16, n_per=2)
        # 3 rounds per mode, INTERLEAVED (A/B/A/B...) so slow host
        # drift hits both modes equally, then min-p99 / max-qps: on a
        # 2-core bench host the 64 client threads contend with the
        # server, so a single round's tail is scheduler noise (the
        # mt_hog_impact_ratio honesty caveat) — min over rounds is the
        # train bench's min-over-runs discipline applied to latency.
        # Measured sequentially-per-server the SAME code read as a
        # ±20% p99 swing in either direction; interleaved, the two
        # modes agree within noise.
        rounds = {mode: [] for mode in servers}
        for _ in range(3):
            for mode, (_, port) in servers.items():
                rounds[mode].append(_hammer_query_server(
                    port, make_body, n_clients=64, n_per=6,
                ))
        for mode, rs in rounds.items():
            out[mode] = {
                "qps": max(r["qps"] for r in rs),
                "p50_ms": min(r["p50_ms"] for r in rs),
                "p99_ms": min(r["p99_ms"] for r in rs),
            }
    finally:
        for srv, _ in servers.values():
            srv.stop()
    out["p99_ratio"] = (
        out["continuous"]["p99_ms"] / out["windowed"]["p99_ms"]
        if out["windowed"]["p99_ms"] > 0 else None
    )
    return out


def _hammer_query_server(port, make_body, n_clients, n_per, timeout=60.0):
    """Shared closed-loop load harness: n_clients keep-alive connections
    each issuing n_per sequential POST /queries.json requests.
    Returns {qps, p50_ms, p99_ms}."""
    import concurrent.futures
    import http.client
    import threading

    def query(conn, i):
        body = make_body(i)
        t0 = time.perf_counter()
        conn.request(
            "POST", "/queries.json", body=body,
            headers={"Content-Type": "application/json"},
        )
        conn.getresponse().read()
        return time.perf_counter() - t0

    warm = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    query(warm, 0)  # warm the serving path + device program
    warm.close()
    lat: list[float] = []
    lock = threading.Lock()

    def client(c):
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
        try:
            for j in range(n_per):
                dt = query(conn, c * n_per + j)
                with lock:
                    lat.append(dt)
        finally:
            conn.close()

    t0 = time.perf_counter()
    with concurrent.futures.ThreadPoolExecutor(n_clients) as pool:
        list(pool.map(client, range(n_clients)))
    wall = time.perf_counter() - t0
    lat.sort()
    return {
        "qps": len(lat) / wall,
        "p50_ms": lat[len(lat) // 2] * 1e3,
        "p99_ms": lat[int(0.99 * (len(lat) - 1))] * 1e3,
    }


def _devprof_serving_crosscheck():
    """Framework-derived serving MFU (obs/devprof: XLA cost_analysis per
    executable × measured device seconds) cross-checked against the hand
    model (2·K·I FLOPs per padded batch row, the same arithmetic this
    file used to own). The bench is now a CONSUMER of the observability
    layer — the hand number only survives as the agreement check
    (ISSUE 3 acceptance: within 2×)."""
    from predictionio_tpu.obs import devprof

    rep = devprof.report()
    rows = [
        e for e in rep["executables"] if e["name"].startswith("als.recommend")
    ]
    if not rows:
        return None
    flops_fw = sum(e["flops_total"] for e in rows)
    secs = sum(e["device_seconds"] for e in rows)
    pad = rep["padding"]
    # hand model: each padded batch row scores the full catalog —
    # one (1, K) · (K, I) contraction (top-k excluded, same as the
    # framework's cost-analysis flops are dominated by the matmul).
    # Warmup dispatches (the bucket ladder) ride outside the padding
    # counters; they are ~100 rows against the hammered thousands.
    flops_hand = 2.0 * RANK * N_ITEMS * pad["rows_padded"]
    peak = rep["platform"].get("peak_flops")
    if not peak or secs <= 0 or flops_hand <= 0:
        return None
    return {
        "mfu_framework": flops_fw / secs / peak,
        "mfu_hand": flops_hand / secs / peak,
        "agreement": flops_fw / flops_hand,
        "device_seconds": secs,
        "invocations": sum(e["invocations"] for e in rows),
        "padding_mean_ratio": pad["mean_padding_ratio"],
        "padding_wasted_gflops": pad["wasted_flops"] / 1e9,
        "batches": pad["batches"],
    }


def bench_serving_framework():
    """The real product path (VERDICT r2 #2): QueryServer over a trained
    recommendation engine — HTTP + JSON extraction + micro-batch
    dispatcher + serving combinator — full item catalog, concurrent
    clients. Returns framework qps / p50 / p99 (ms)."""

    from predictionio_tpu.data.event import Event
    from predictionio_tpu.data.storage.base import App
    from predictionio_tpu.data.storage.registry import (
        SourceConfig,
        Storage,
        StorageConfig,
    )
    from predictionio_tpu.workflow.core import run_train
    from predictionio_tpu.workflow.server import (
        QueryServer,
        QueryServerConfig,
        latest_completed_runtime,
    )

    cfg = StorageConfig(
        sources={"MEM": SourceConfig("MEM", "memory", {})},
        repositories={
            "METADATA": "MEM", "EVENTDATA": "MEM", "MODELDATA": "MEM",
        },
    )
    storage = Storage(cfg)
    apps = storage.get_meta_data_apps()
    app_id = apps.insert(App(0, "benchapp"))
    events = storage.get_events()
    events.init_app(app_id)

    # serving-shape catalog: every item id appears so the model covers the
    # full N_ITEMS catalog; a modest user count keeps the seed train fast
    n_users_serve = 2_000 if not SMALL else 200
    rng = np.random.RandomState(11)
    batch: list[Event] = []
    for i in range(N_ITEMS):
        u = int(rng.randint(n_users_serve))
        batch.append(Event(
            event="rate", entity_type="user", entity_id=f"u{u}",
            target_entity_type="item", target_entity_id=f"i{i}",
            properties={"rating": float(rng.randint(1, 6))},
        ))
    for _ in range(n_users_serve * 20):
        u = int(rng.randint(n_users_serve))
        i = int(rng.zipf(1.4)) % N_ITEMS
        batch.append(Event(
            event="rate", entity_type="user", entity_id=f"u{u}",
            target_entity_type="item", target_entity_id=f"i{i}",
            properties={"rating": float(rng.randint(1, 6))},
        ))
    for lo in range(0, len(batch), 10_000):
        events.insert_batch(batch[lo:lo + 10_000], app_id)

    variant = {
        "id": "benchrec",
        "engineFactory":
            "predictionio_tpu.engines.recommendation.RecommendationEngine",
        "datasource": {"params": {"app_name": "benchapp"}},
        "algorithms": [
            {"name": "als", "params": {"rank": RANK, "num_iterations": 5}}
        ],
    }
    run_train(storage, variant)
    runtime = latest_completed_runtime(storage, "benchrec", "0", "benchrec")
    srv = QueryServer(
        storage, runtime, QueryServerConfig(ip="127.0.0.1", port=0)
    )
    # span tracing (ISSUE 2): keep EVERY request's trace for the run so
    # the ledger can embed the slowest request's stage breakdown — the
    # default tail-sampling knobs would race eviction under 1k+ requests
    from predictionio_tpu.obs.spans import get_default_recorder

    recorder = get_default_recorder()
    recorder.sample_rate = 1.0
    recorder.max_traces = 4096
    port = srv.start()
    try:
        # client sweep (VERDICT r4 #5): closed-loop clients bound the
        # batch the dispatcher can fill — each device round trip serves
        # at most n_clients queries, so qps should scale with clients
        # until max_batch (64) saturates
        sweep = []
        for n_clients in (32, 64, 128):
            stats = _hammer_query_server(
                port,
                lambda i: json.dumps(
                    {"user": f"u{i % n_users_serve}", "num": 10}
                ).encode(),
                n_clients=n_clients,
                n_per=8 if n_clients <= 64 else 5,
            )
            sweep.append(dict(stats, clients=n_clients))
        best = max(sweep, key=lambda r: r["qps"])
        monitor_cost = _bench_monitor_overhead(srv, port, n_users_serve)
        swap = _bench_hot_swap(srv, storage, port, n_users_serve)
        online = _bench_online(srv, storage, port, app_id, n_users_serve)
        return dict(
            best, sweep=sweep, obs=_registry_snapshot(srv.metrics),
            slowest_trace=_slowest_trace_summary(recorder),
            devprof=_devprof_serving_crosscheck(),
            **monitor_cost,
            **swap,
            **online,
        )
    finally:
        srv.stop()


def _bench_online(srv, storage, port, app_id, n_users_serve):
    """Online-learning cost + value (ISSUE 9 acceptance): with the
    stream consumer attached, (a) event-ingest→serving-visibility
    latency for COLD-START users — insert a brand-new user's events and
    poll /queries.json until the answer is personalized (an unknown user
    returns an empty result, so non-empty == folded); the bar is a
    personalized answer within 2 consumer ticks — and (b) serving p99
    with the consumer ATTACHED (ticking, stream idle) vs fully detached
    (bar: `online_overhead_p99_ratio` < 1.05 — attachment must be free,
    like the monitor plane). `online_folding_p99_ratio` additionally
    reports p99 while the consumer actively folds a 20 ev/s trickle —
    on the 2-core bench host the consumer's solve CPU contends directly
    with the 32 client threads (same caveat as mt_hog_impact_ratio), so
    that number is the honest contended cost, not the attachment bar."""
    import threading as _threading
    import urllib.request

    from predictionio_tpu.data.event import Event
    from predictionio_tpu.online import OnlineConsumerConfig

    events = storage.get_events()

    def make_body(i):
        return json.dumps(
            {"user": f"u{i % n_users_serve}", "num": 10}
        ).encode()

    def hammer():
        # best of two LONG passes: at 32×8 requests the p99 is the ~3rd
        # slowest request — pure scheduler noise on the 2-core host (the
        # idle-attached ratio measured 0.8×–1.7× run to run). 32×16 per
        # pass + min-of-2 on BOTH sides of every ratio keeps the
        # comparison about the consumer, not the scheduler's mood
        a = _hammer_query_server(port, make_body, n_clients=32, n_per=16)
        b = _hammer_query_server(port, make_body, n_clients=32, n_per=16)
        return a if a["p99_ms"] <= b["p99_ms"] else b

    def ask(uid):
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/queries.json",
            data=json.dumps({"user": uid, "num": 5}).encode(),
            headers={"Content-Type": "application/json"}, method="POST",
        )
        try:
            with urllib.request.urlopen(req, timeout=10) as r:
                return json.loads(r.read().decode())
        except Exception:
            return {}

    off = hammer()

    tick_s = 0.2
    srv.attach_online(
        app_id, OnlineConsumerConfig(tick_s=tick_s, from_latest=True)
    )
    try:
        # (a) cold-start visibility latency — these folds also pre-warm
        # the fold-in kernel's bucket shapes before any p99 measurement
        lat = []
        for c in range(5):
            uid = f"coldstart{c}"
            t0 = time.perf_counter()
            events.insert_batch([
                Event(
                    event="rate", entity_type="user", entity_id=uid,
                    target_entity_type="item", target_entity_id=f"i{j}",
                    properties={"rating": 5.0},
                )
                for j in range(3)
            ], app_id)
            deadline = time.monotonic() + 30.0
            while time.monotonic() < deadline:
                if (ask(uid) or {}).get("item_scores"):
                    lat.append(time.perf_counter() - t0)
                    break
                time.sleep(0.02)
        # warm the multi-user fold shape (the trickle below re-solves
        # batches of existing users: r_pad=8/64 buckets) so no p99
        # measurement eats one-time XLA compiles — and WAIT until the
        # burst is fully consumed before measuring anything
        consumed_target = srv.online.counters["events_consumed"] + 24
        events.insert_batch([
            Event(
                event="rate", entity_type="user", entity_id=f"u{u}",
                target_entity_type="item", target_entity_id=f"i{u % 50}",
                properties={"rating": 4.0},
            )
            for u in range(24)
        ], app_id)
        deadline = time.monotonic() + 30.0
        while (
            srv.online.counters["events_consumed"] < consumed_target
            and time.monotonic() < deadline
        ):
            time.sleep(tick_s / 2)
        time.sleep(tick_s * 2)  # let the publish settle

        # (b) attachment cost: consumer ticking, stream idle — the bar
        attached = hammer()

        # (c) honest contended cost: consumer folding a live trickle
        stop_feed = _threading.Event()

        def feed():
            n = 0
            while not stop_feed.is_set():
                n += 1
                events.insert(Event(
                    event="rate", entity_type="user",
                    entity_id=f"u{n % n_users_serve}",
                    target_entity_type="item",
                    target_entity_id=f"i{n % 50}",
                    properties={"rating": 4.0},
                ), app_id)
                stop_feed.wait(0.05)

        feeder = _threading.Thread(target=feed, daemon=True)
        feeder.start()
        folding = hammer()
        stop_feed.set()
        feeder.join(timeout=5)
        counters = dict(srv.online.counters)
    finally:
        srv.online.stop()
        srv.online = None
    lat_ms = sorted(x * 1000.0 for x in lat)
    p50 = lat_ms[len(lat_ms) // 2] if lat_ms else None

    def _ratio(on):
        return (
            round(on["p99_ms"] / off["p99_ms"], 4)
            if off["p99_ms"] > 0 else None
        )

    return {
        "online_tick_s": tick_s,
        "online_fold_latency_p50_ms": (
            None if p50 is None else round(p50, 1)
        ),
        "online_fold_latency_max_ms": (
            round(lat_ms[-1], 1) if lat_ms else None
        ),
        "online_fold_latency_ticks": (
            None if p50 is None else round(p50 / (tick_s * 1000.0), 2)
        ),
        "online_cold_users_visible": len(lat_ms),
        "online_events_folded": counters.get("events_folded", 0),
        "online_off_p99_ms": round(off["p99_ms"], 3),
        "online_on_p99_ms": round(attached["p99_ms"], 3),
        "online_overhead_p99_ratio": _ratio(attached),
        "online_folding_p99_ms": round(folding["p99_ms"], 3),
        "online_folding_p99_ratio": _ratio(folding),
    }


def _bench_monitor_overhead(srv, port, n_users_serve):
    """Monitoring-plane cost (ISSUE 8 acceptance): serving p99 with the
    TSDB sampler + SLO engine running at AGGRESSIVE knobs (1 s sampling
    + 1 s burn-rate evaluation — 5×/15× the defaults) vs fully
    detached. The bar: `monitor_overhead_p99_ratio` stays under 1.05 —
    history and alerting must be free at serving time."""
    from predictionio_tpu.obs.monitor import SLOSpec, get_monitor

    monitor = get_monitor()

    def make_body(i):
        return json.dumps(
            {"user": f"u{i % n_users_serve}", "num": 10}
        ).encode()

    def hammer():
        return _hammer_query_server(
            port, make_body, n_clients=32, n_per=8
        )

    saved_intervals = (monitor.sampler_interval_s, monitor.slo_interval_s)
    # OFF: the server detaches from the sampler entirely
    token, srv._monitor_token = srv._monitor_token, None
    monitor.detach(token)
    off = hammer()
    # ON: reattach with 1 s sampling + 1 s SLO evaluation over two SLOs
    monitor.sampler_interval_s = 1.0
    monitor.slo_interval_s = 1.0
    monitor.set_slos([
        SLOSpec(
            name="bench-availability", kind="availability",
            objective=0.99, fast_window_s=30.0, window_s=120.0,
        ),
        SLOSpec(
            name="bench-latency", kind="latency", objective=0.95,
            threshold_ms=250.0, fast_window_s=30.0, window_s=120.0,
        ),
    ])
    srv._monitor_token = monitor.attach("query", srv.metrics)
    on = hammer()
    # restore the default posture: the hot-swap section (and any later
    # bench server) must measure under normal knobs, not the 5x/15x-
    # aggressive ones this comparison deliberately provoked
    token, srv._monitor_token = srv._monitor_token, None
    monitor.detach(token)
    monitor.sampler_interval_s, monitor.slo_interval_s = saved_intervals
    monitor.set_slos([])
    srv._monitor_token = monitor.attach("query", srv.metrics)
    ratio = (
        on["p99_ms"] / off["p99_ms"] if off["p99_ms"] > 0 else None
    )
    return {
        "monitor_off_p99_ms": round(off["p99_ms"], 3),
        "monitor_on_p99_ms": round(on["p99_ms"], 3),
        "monitor_overhead_p99_ratio": (
            None if ratio is None else round(ratio, 4)
        ),
        "monitor_on_qps": round(on["qps"], 1),
        "monitor_off_qps": round(off["qps"], 1),
        "monitor_tsdb_series": monitor.tsdb.series_count(),
    }


def _bench_hot_swap(srv, storage, port, n_users_serve):
    """Hot-swap cost (ISSUE 5 satellite): canary the served model's own
    blob as a candidate, then promote it mid-way through a 128-client
    closed-loop run. `swap_p99_ms` is the run's p99 WITH a promote in
    the middle; `swap_dropped` counts queries that failed or got no
    response — the zero-drop contract says it must be 0."""
    import http.client
    import threading
    import concurrent.futures

    from predictionio_tpu.deploy.registry import ModelRegistry

    version = ModelRegistry(storage).register(srv.runtime.instance)
    srv.start_rollout({
        "version": version.id, "fraction": 0.3,
        # the verdict loop must not act on its own — the bench promotes
        "bake_s": 3600.0, "min_requests": 10**9, "interval_s": 60.0,
    })
    n_clients, n_per = 128, 5 if not SMALL else 2
    total = n_clients * n_per
    lat: list[float] = []
    dropped = 0
    done = 0
    lock = threading.Lock()
    promoted = threading.Event()

    def client(c):
        nonlocal dropped, done
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60.0)
        try:
            for j in range(n_per):
                body = json.dumps({
                    "user": f"u{(c * n_per + j) % n_users_serve}",
                    "num": 10,
                }).encode()
                t0 = time.perf_counter()
                try:
                    conn.request(
                        "POST", "/queries.json", body=body,
                        headers={"Content-Type": "application/json"},
                    )
                    resp = conn.getresponse()
                    resp.read()
                    ok = resp.status == 200
                except Exception:
                    ok = False
                    conn.close()
                    conn = http.client.HTTPConnection(
                        "127.0.0.1", port, timeout=60.0
                    )
                dt = time.perf_counter() - t0
                with lock:
                    done += 1
                    lat.append(dt)
                    if not ok:
                        dropped += 1
                    if done >= total // 3 and not promoted.is_set():
                        promoted.set()  # swap lands mid-run, under load
                        threading.Thread(
                            target=srv.rollout.promote,
                            args=("bench hot-swap",), daemon=True,
                        ).start()
        finally:
            conn.close()

    with concurrent.futures.ThreadPoolExecutor(n_clients) as pool:
        list(pool.map(client, range(n_clients)))
    # the promote thread is quick, but make sure it finished before stop
    for _ in range(100):
        if srv.rollout is not None and srv.rollout.st.state == "promoted":
            break
        time.sleep(0.05)
    lat.sort()
    return {
        "swap_p99_ms": lat[int(0.99 * (len(lat) - 1))] * 1e3 if lat else 0.0,
        "swap_dropped": dropped,
        "swap_requests": len(lat),
        "swap_state": srv.rollout.st.state if srv.rollout else "none",
    }


def bench_multitenant():
    """Multi-tenant serving (ISSUE 6): 1 hog + 3 well-behaved tenants on
    ONE query server. Measures isolation (well-behaved p99 vs its solo
    baseline, goodput spread across the well-behaved set, zero in-quota
    drops) and model-cache economics (6 tenants through a 3-slot cache:
    hit rate + transparent reload count)."""
    import concurrent.futures
    import http.client
    import threading

    from predictionio_tpu.data.event import Event
    from predictionio_tpu.data.storage.base import App
    from predictionio_tpu.data.storage.registry import (
        SourceConfig,
        Storage,
        StorageConfig,
    )
    from predictionio_tpu.tenancy import Tenant, TenantMux, TenantStore
    from predictionio_tpu.workflow.core import run_train
    from predictionio_tpu.workflow.server import (
        QueryServer,
        QueryServerConfig,
        latest_completed_runtime,
    )

    cfg = StorageConfig(
        sources={"MEM": SourceConfig("MEM", "memory", {})},
        repositories={
            "METADATA": "MEM", "EVENTDATA": "MEM", "MODELDATA": "MEM",
        },
    )
    storage = Storage(cfg)
    app_id = storage.get_meta_data_apps().insert(App(0, "mtbench"))
    events = storage.get_events()
    events.init_app(app_id)
    n_users, n_items = (500, 2000) if not SMALL else (100, 400)
    rng = np.random.RandomState(17)
    batch: list[Event] = []
    for i in range(n_items):
        batch.append(Event(
            event="rate", entity_type="user",
            entity_id=f"u{int(rng.randint(n_users))}",
            target_entity_type="item", target_entity_id=f"i{i}",
            properties={"rating": float(rng.randint(1, 6))},
        ))
    for _ in range(n_users * 10):
        batch.append(Event(
            event="rate", entity_type="user",
            entity_id=f"u{int(rng.randint(n_users))}",
            target_entity_type="item",
            target_entity_id=f"i{int(rng.zipf(1.4)) % n_items}",
            properties={"rating": float(rng.randint(1, 6))},
        ))
    for lo in range(0, len(batch), 10_000):
        events.insert_batch(batch[lo:lo + 10_000], app_id)
    variant = {
        "id": "mtbench",
        "engineFactory":
            "predictionio_tpu.engines.recommendation.RecommendationEngine",
        "datasource": {"params": {"app_name": "mtbench"}},
        "algorithms": [
            {"name": "als", "params": {"rank": RANK, "num_iterations": 3}}
        ],
    }
    run_train(storage, variant)

    store = TenantStore(storage)
    goods = ["good1", "good2", "good3"]
    # the hog gets qps + concurrency quotas (its overage 429s instead of
    # queueing — admission control is half the isolation story, the
    # weighted-fair batching is the other half); the well-behaved
    # tenants are unlimited — every one of their queries is in-quota
    # and must be answered
    store.upsert(Tenant(
        id="hog", engine_id="mtbench", qps=200.0, max_concurrency=8,
        # the device-seconds cap is the quota that actually protects
        # neighbors on a saturated device: the hog may burn at most
        # ~15% of one device's seconds per wall second
        device_seconds_per_s=0.15,
    ))
    for g in goods:
        store.upsert(Tenant(id=g, engine_id="mtbench"))

    def hammer_tenant(port, tenant, n_clients, n_per, results, label):
        """Closed-loop per-tenant load; records (latency, status)."""
        def client(c):
            conn = http.client.HTTPConnection(
                "127.0.0.1", port, timeout=60.0
            )
            try:
                for j in range(n_per):
                    body = json.dumps({
                        "user": f"u{(c * n_per + j) % n_users}", "num": 10,
                    }).encode()
                    t0 = time.perf_counter()
                    try:
                        conn.request(
                            "POST", f"/tenants/{tenant}/queries.json",
                            body=body,
                            headers={"Content-Type": "application/json"},
                        )
                        resp = conn.getresponse()
                        resp.read()
                        status = resp.status
                    except Exception:
                        status = 0
                        conn.close()
                        conn = http.client.HTTPConnection(
                            "127.0.0.1", port, timeout=60.0
                        )
                    results[label].append(
                        (time.perf_counter() - t0, status)
                    )
            finally:
                conn.close()
        with concurrent.futures.ThreadPoolExecutor(n_clients) as pool:
            list(pool.map(client, range(n_clients)))

    def p99_ms(rows):
        lat = sorted(r[0] for r in rows if r[1] == 200)
        return lat[int(0.99 * (len(lat) - 1))] * 1e3 if lat else 0.0

    # -- phase 1+2: isolation under a hog --------------------------------
    runtime = latest_completed_runtime(storage, "mtbench", "0", "mtbench")
    # max_window is tuned down for multi-tenant serving: the adaptive
    # drain-linger exists to deepen SINGLE-runtime batches, but tenant
    # groups dispatch per-runtime anyway, so lingering 60 ms only adds
    # queue wait to every tenant's p99 without merging any device work
    srv = QueryServer(
        storage, runtime,
        QueryServerConfig(ip="127.0.0.1", port=0, max_window_ms=8.0),
    )
    mux = TenantMux(
        storage, metrics=srv.metrics, cache_capacity=8, refresh_s=1.0,
        sync_s=3600.0,
    )
    srv.attach_tenancy(mux)
    port = srv.start()
    try:
        import collections

        results: dict = collections.defaultdict(list)
        n_per = 25 if not SMALL else 4
        # warm every tenant first: the first query per tenant pays the
        # model-cache load (by design) and the jit bucket ladder — the
        # isolation measurement is about steady-state scheduling, not
        # cold starts
        for t in ("good1", "good2", "good3", "hog"):
            hammer_tenant(port, t, 1, 2, results, "warmup")
        # solo baseline: one well-behaved tenant, quiet server
        hammer_tenant(port, "good1", 4, n_per, results, "solo")
        solo_p99 = p99_ms(results["solo"])

        # no-hog baseline: all three good tenants at their normal pace.
        # On small hosts the closed-loop client threads themselves
        # contend with the server for CPU, so the hog's MARGINAL impact
        # (contended vs this) is the honest isolation number next to
        # the raw solo ratio
        threads = [
            threading.Thread(
                target=hammer_tenant,
                args=(port, g, 4, n_per, results, f"nohog-{g}"),
            )
            for g in goods
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        nohog_p99 = max(p99_ms(results[f"nohog-{g}"]) for g in goods)

        # contended: the hog floods while the three good tenants keep
        # their modest pace — weighted-fair batching + quota admission
        # are what keeps the good tenants' numbers flat
        # 12 hog clients: enough to keep the hog's concurrency quota
        # saturated (8) and its qps overage 429ing, without drowning a
        # small host in client threads that steal the server's own CPU
        hog_clients = 12 if not SMALL else 8
        threads = [threading.Thread(
            target=hammer_tenant,
            args=(port, "hog", hog_clients, n_per * 2, results, "hog"),
        )]
        for g in goods:
            threads.append(threading.Thread(
                target=hammer_tenant,
                args=(port, g, 4, n_per, results, g),
            ))
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0

        good_p99 = {g: p99_ms(results[g]) for g in goods}
        goodput = {
            g: sum(1 for r in results[g] if r[1] == 200) / wall
            for g in goods
        }
        in_quota_dropped = sum(
            1 for g in goods for r in results[g] if r[1] != 200
        )
        hog_ok = sum(1 for r in results["hog"] if r[1] == 200)
        hog_429 = sum(1 for r in results["hog"] if r[1] == 429)
        worst_p99 = max(good_p99.values())
        isolation = {
            "solo_p99_ms": round(solo_p99, 1),
            "nohog_p99_ms": round(nohog_p99, 1),
            "contended_p99_ms": round(worst_p99, 1),
            "p99_ratio": round(worst_p99 / solo_p99, 2) if solo_p99 else 0,
            "hog_impact_ratio": round(
                worst_p99 / nohog_p99, 2
            ) if nohog_p99 else 0,
            "goodput_qps": {
                g: round(q, 1) for g, q in goodput.items()
            },
            "goodput_ratio": round(
                max(goodput.values()) / min(goodput.values()), 2
            ) if min(goodput.values()) > 0 else 0,
            "in_quota_dropped": in_quota_dropped,
            "hog_served": hog_ok,
            "hog_rejected_429": hog_429,
            "hog_goodput_qps": round(hog_ok / wall, 1),
        }
    finally:
        srv.stop()

    # -- phase 3: cache economics — 6 live models through 3 slots --------
    cache_tenants = [f"cache{i}" for i in range(6)]
    for c in cache_tenants:
        store.upsert(Tenant(id=c, engine_id="mtbench"))
    runtime = latest_completed_runtime(storage, "mtbench", "0", "mtbench")
    srv = QueryServer(
        storage, runtime, QueryServerConfig(ip="127.0.0.1", port=0)
    )
    mux = TenantMux(
        storage, metrics=srv.metrics, cache_capacity=3, refresh_s=1.0,
        sync_s=3600.0,
    )
    srv.attach_tenancy(mux)
    port = srv.start()
    try:
        import collections

        results = collections.defaultdict(list)
        # zipf-ish access skew: hot tenants mostly hit, cold ones cycle
        # through the LRU — the shape a real fleet has
        passes = 3 if not SMALL else 2
        order = []
        for p in range(passes):
            for i, c in enumerate(cache_tenants):
                order += [c] * (3 if i < 2 else 1)
        for c in order:
            hammer_tenant(port, c, 1, 1, results, c)
        served = sum(
            1 for c in cache_tenants for r in results[c] if r[1] == 200
        )
        stats = mux.cache.stats()
        cache_out = {
            "live_models": len(cache_tenants),
            "capacity": stats["capacity"],
            "served": served,
            "hit_rate": round(stats["hit_rate"], 3),
            "reloads": stats["reloads"],
            "evictions": stats["evictions"],
            "resident": stats["resident"],
        }
        assert served == len(order), "cache phase dropped queries"
    finally:
        srv.stop()
    return {"isolation": isolation, "cache": cache_out}


def _slowest_trace_summary(recorder):
    """Per-stage span breakdown of the slowest sampled /queries.json
    request (ISSUE 2): where the tail request actually spent its time —
    micro-batch queue, device dispatch, or serve/transfer — straight off
    the span recorder, so the ledger's p99 has an explanation attached."""
    slowest = None
    for s in recorder.summaries(limit=0):
        if s.get("path") != "/queries.json":
            continue
        if slowest is None or s["duration_ms"] > slowest["duration_ms"]:
            slowest = s
    if slowest is None:
        return None
    stages: dict = {}
    for sp in recorder.get_trace(slowest["trace_id"]):
        if sp.name == "server.request":
            continue
        # SUM repeated names (several sequential storage RPCs must read
        # as their total, not the longest one) so the breakdown tracks
        # total_ms
        stages[sp.name] = round(
            stages.get(sp.name, 0.0) + sp.duration * 1e3, 3
        )
    return {
        "trace_id": slowest["trace_id"],
        "total_ms": slowest["duration_ms"],
        "stage_ms": stages,
    }


def _registry_snapshot(registry):
    """Server-side registry view of the whole bench run (ISSUE 1): the
    ledger records full latency DISTRIBUTIONS (p50/p95/p99 from histogram
    buckets) and batch-depth shape, not just the client-side wall-clock
    means `_hammer_query_server` computes."""

    from predictionio_tpu.obs import BATCH_SIZE_BUCKETS

    def ms(h, q):
        return round(h.quantile(q) * 1e3, 3)

    serve = registry.histogram("serve_seconds")
    predict = registry.histogram("predict_seconds")
    batch = registry.histogram(
        "batch_size", buckets=BATCH_SIZE_BUCKETS, lower_bound=1
    )
    wait = registry.histogram("batch_queue_wait_seconds")
    return {
        "requests": serve.count,
        "serve_ms": {"p50": ms(serve, 0.5), "p95": ms(serve, 0.95),
                     "p99": ms(serve, 0.99)},
        "predict_ms": {"p50": ms(predict, 0.5), "p95": ms(predict, 0.95),
                       "p99": ms(predict, 0.99)},
        "queue_wait_ms": {"p50": ms(wait, 0.5), "p99": ms(wait, 0.99)},
        "batches": batch.count,
        "batch_size": {"p50": round(batch.quantile(0.5), 1),
                       "p95": round(batch.quantile(0.95), 1),
                       "mean": round(batch.mean, 2)},
    }


def bench_event_ingestion():
    """Events/sec through POST /batch/events.json with 4 concurrent
    writers into a sqlite-backed EventServer (VERDICT r3 #9: ingestion
    had no number on the ledger; reference batch path
    EventServer.scala:374-440)."""
    import concurrent.futures
    import tempfile
    import urllib.request

    from predictionio_tpu.data.api.server import (
        EventServer,
        EventServerConfig,
    )
    from predictionio_tpu.data.storage.base import AccessKey, App
    from predictionio_tpu.data.storage.registry import (
        SourceConfig,
        Storage,
        StorageConfig,
    )

    tmp = tempfile.mkdtemp(prefix="pio_ingest_bench")
    cfg = StorageConfig(
        sources={
            "SQL": SourceConfig("SQL", "sqlite", {"PATH": f"{tmp}/pio.db"})
        },
        repositories={
            "METADATA": "SQL", "EVENTDATA": "SQL", "MODELDATA": "SQL",
        },
    )
    storage = Storage(cfg)
    app_id = storage.get_meta_data_apps().insert(App(0, "ingestbench"))
    storage.get_events().init_app(app_id)
    storage.get_meta_data_access_keys().insert(
        AccessKey(key="BENCHKEY", app_id=app_id, events=())
    )
    srv = EventServer(storage, EventServerConfig(ip="127.0.0.1", port=0))
    port = srv.start()
    n_writers, batches_per, batch_size = 4, 25 if SMALL else 120, 50
    rng = np.random.RandomState(2)

    def make_batch(w, b):
        return json.dumps([
            {
                "event": "rate",
                "entityType": "user",
                "entityId": f"u{int(rng.randint(10_000))}",
                "targetEntityType": "item",
                "targetEntityId": f"i{int(rng.randint(5_000))}",
                "properties": {"rating": float(rng.randint(1, 6))},
            }
            for _ in range(batch_size)
        ]).encode()

    payloads = [
        [make_batch(w, b) for b in range(batches_per)]
        for w in range(n_writers)
    ]
    url = f"http://127.0.0.1:{port}/batch/events.json?accessKey=BENCHKEY"

    def writer(w):
        for body in payloads[w]:
            req = urllib.request.Request(
                url, data=body,
                headers={"Content-Type": "application/json"}, method="POST",
            )
            with urllib.request.urlopen(req, timeout=60) as r:
                r.read()

    try:
        writer(0)  # warm (also re-used payloads are fine: ids collide ok)
        t0 = time.perf_counter()
        with concurrent.futures.ThreadPoolExecutor(n_writers) as pool:
            list(pool.map(writer, range(n_writers)))
        wall = time.perf_counter() - t0
        total = n_writers * batches_per * batch_size
        return {"events_per_sec": total / wall, "events": total,
                "writers": n_writers, "backend": "sqlite"}
    finally:
        srv.stop()


def bench_data_plane():
    """ISSUE 13: the columnar data plane — segmentfs batch ingest vs the
    sqlite store on the same host (store-level, no HTTP, so the number
    is the STORAGE layer's), sharded-over-segmentfs vs single-store on
    this host, the row-path vs segment-path loader A/B (host prep +
    device transfer, plus the tail-only retrain restage), and the
    find_since tail-read latency a streaming consumer pays per tick."""
    import datetime as _dt
    import tempfile

    import jax

    from predictionio_tpu.data.datamap import DataMap
    from predictionio_tpu.data.event import Event
    from predictionio_tpu.data.storage.base import EventQuery
    from predictionio_tpu.data.storage.segmentfs import SegmentFSEventStore
    from predictionio_tpu.data.storage.sharded import ShardedEventStore
    from predictionio_tpu.data.storage.sqlite import SqliteEventStore
    from predictionio_tpu.data.store.columnar import EventFrame
    from predictionio_tpu.parallel.loader import SegmentStager

    n_events = 50_000 if SMALL else 400_000
    batch = 1_000
    rng = np.random.RandomState(11)
    t0_dt = _dt.datetime(2024, 1, 1, tzinfo=_dt.timezone.utc)
    users = rng.randint(0, 20_000, n_events)
    items = rng.randint(0, 5_000, n_events)
    ratings = rng.randint(1, 6, n_events)
    events = [
        Event(
            event="rate", entity_type="user", entity_id=f"u{int(u)}",
            target_entity_type="item", target_entity_id=f"i{int(i)}",
            properties=DataMap({"rating": float(r)}),
            event_time=t0_dt + _dt.timedelta(seconds=k // 10),
        )
        for k, (u, i, r) in enumerate(zip(users, items, ratings))
    ]
    chunks = [
        events[i : i + batch] for i in range(0, n_events, batch)
    ]

    n_writers = 4  # concurrent ingest clients, the production shape

    def ingest_once(store) -> float:
        """Concurrent batch ingest: `n_writers` threads striping the
        chunk list — the event server's thread-pool shape. A single
        store serializes every writer on one lock + one WAL fsync; the
        sharded composite's per-child locks let writers overlap, which
        is the scaling story the r05 HTTP+sqlite stack inverted."""
        import concurrent.futures

        store.init_app(1)

        def writer(w):
            for chunk in chunks[w::n_writers]:
                store.insert_batch(chunk, 1)

        t0 = time.perf_counter()
        with concurrent.futures.ThreadPoolExecutor(n_writers) as pool:
            list(pool.map(writer, range(n_writers)))
        return n_events / (time.perf_counter() - t0)

    def ingest_median(makers: dict, runs: int = 3) -> dict:
        """Interleaved median-of-N fresh-store runs: configs alternate
        within each round so shared-host noise phases hit them all
        equally — an unpaired best-of scheme made the single-vs-sharded
        RATIO swing ±30% run to run."""
        results: dict[str, list[float]] = {k: [] for k in makers}
        for r in range(runs):
            for k, mk in makers.items():
                store = mk(f"{k}{r}")
                try:
                    results[k].append(ingest_once(store))
                finally:
                    store.close()
        return {k: float(np.median(v)) for k, v in results.items()}

    tmp = tempfile.mkdtemp(prefix="pio_dataplane_")
    # warm the interpreter/allocator on a throwaway store first — the
    # first config timed otherwise reads ~15% cold (run-order artifact)
    warm = SegmentFSEventStore({"PATH": f"{tmp}/warm"})
    warm.init_app(1)
    for chunk in chunks[:10]:
        warm.insert_batch(chunk, 1)
    warm.close()

    # sharded composite over two segmentfs children, same host/cores —
    # the configuration that REGRESSED below single-store on the r05
    # HTTP+sqlite stack
    med = ingest_median({
        "sqlite": lambda r: SqliteEventStore(
            {"PATH": f"{tmp}/{r}.db"}
        ),
        "segment": lambda r: SegmentFSEventStore({"PATH": f"{tmp}/{r}"}),
        "sharded": lambda r: ShardedEventStore(
            stores=[
                SegmentFSEventStore({"PATH": f"{tmp}/{r}_{i}"})
                for i in range(2)
            ]
        ),
    })
    sqlite_eps = med["sqlite"]
    segment_eps = med["segment"]
    sharded_eps = med["sharded"]

    # the same comparison at the event server's REAL batch size (the
    # /batch/events.json POST is ~50 events): this is the shape whose
    # r05 sharded number regressed to ~half of single-store
    chunks_big = chunks
    chunks = [events[i : i + 50] for i in range(0, n_events, 50)]
    med50 = ingest_median({
        "segment": lambda r: SegmentFSEventStore(
            {"PATH": f"{tmp}/b50{r}"}
        ),
        "sharded": lambda r: ShardedEventStore(
            stores=[
                SegmentFSEventStore({"PATH": f"{tmp}/b50{r}_{i}"})
                for i in range(2)
            ]
        ),
    })
    single_b50_eps = med50["segment"]
    sharded_b50_eps = med50["sharded"]
    chunks = chunks_big

    # loader A/B on the segmentfs corpus: row path folds Events through
    # Python; segment path is column concat + vectorized remap. Sealing
    # is driven EXPLICITLY (long interval) so a background seal/compact
    # between the two stage() calls can't change the segment token and
    # turn the sealed-reuse assertion flaky.
    seg = SegmentFSEventStore(
        {"PATH": f"{tmp}/loader", "SEAL_INTERVAL_S": "3600"}
    )
    seg.init_app(1)
    for chunk in chunks:
        seg.insert_batch(chunk, 1)
    sql = SqliteEventStore({"PATH": f"{tmp}/tail.db"})
    sql.init_app(1)
    for chunk in chunks:
        sql.insert_batch(chunk, 1)
    seg.seal(1)
    query = EventQuery(app_id=1, event_names=["rate"])
    # best-of-3 on both host-prep paths (shared-host noise); the segment
    # path is measured COLD each run (cache dropped) — the cache-hit
    # case is the separate retrain_restage number
    row_prep_s = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        row_frame = EventFrame.from_events(
            seg.find(query), value_prop="rating"
        )
        row_prep_s = min(row_prep_s, time.perf_counter() - t0)
    seg_prep_s = float("inf")
    for _ in range(3):
        seg._frame_cache.clear()
        t0 = time.perf_counter()
        seg_frame, _token, _n = seg.find_frame_parts(
            query, value_prop="rating"
        )
        seg_prep_s = min(seg_prep_s, time.perf_counter() - t0)
    assert len(seg_frame) == len(row_frame)

    t0 = time.perf_counter()
    staged_row = [
        jax.device_put(np.asarray(a))
        for a in (
            row_frame.entity_idx, row_frame.target_idx, row_frame.value,
        )
    ]
    jax.block_until_ready(staged_row)
    row_transfer_s = time.perf_counter() - t0

    stager = SegmentStager()
    t0 = time.perf_counter()
    _f, staged = stager.stage(seg, query, value_prop="rating")
    jax.block_until_ready(list(staged.values()))
    seg_transfer_s = time.perf_counter() - t0
    # the retrain shape: fresh tail lands, sealed columns stay resident
    seg.insert_batch(events[:batch], 1)
    t0 = time.perf_counter()
    _f2, staged2 = stager.stage(seg, query, value_prop="rating")
    jax.block_until_ready(list(staged2.values()))
    retrain_restage_s = time.perf_counter() - t0
    assert stager.stats["sealed_reuse"] == 1

    # consumer tail read: one page off the head of the stream
    def tail_p50_ms(store) -> float:
        cursor = store.latest_revision(1) - 512
        lats = []
        for _ in range(50):
            t0 = time.perf_counter()
            page = store.find_since(1, cursor, limit=512)
            lats.append((time.perf_counter() - t0) * 1000)
            assert len(page) >= 512 - 1
        return float(np.percentile(lats, 50))

    seg_tail_ms = tail_p50_ms(seg)
    sql_tail_ms = tail_p50_ms(sql)

    seg.close()
    sql.close()
    return {
        "events": n_events,
        "ingest_sqlite_store_eps": sqlite_eps,
        "ingest_segment_eps": segment_eps,
        "ingest_segment_vs_sqlite": segment_eps / sqlite_eps,
        "ingest_sharded_segment_eps": sharded_eps,
        "ingest_sharded_segment_vs_single": sharded_eps / segment_eps,
        "ingest_segment_b50_eps": single_b50_eps,
        "ingest_sharded_segment_b50_eps": sharded_b50_eps,
        "ingest_sharded_segment_vs_single_b50":
            sharded_b50_eps / single_b50_eps,
        "loader_rows": len(row_frame),
        "loader_row_host_prep_s": row_prep_s,
        "loader_host_prep_s": seg_prep_s,
        "loader_host_prep_speedup": row_prep_s / max(seg_prep_s, 1e-9),
        "loader_row_transfer_s": row_transfer_s,
        "loader_transfer_s": seg_transfer_s,
        "loader_retrain_restage_s": retrain_restage_s,
        "find_since_tail_p50_ms": seg_tail_ms,
        "find_since_tail_sqlite_p50_ms": sql_tail_ms,
    }


def bench_ur_framework():
    """The north-star UR workload through the REAL product path
    (VERDICT r3 #4): universal-engine queries — history fetch, exclusion
    build, device batch score — through a QueryServer under 32
    concurrent clients at a 1e5-item catalog."""
    from predictionio_tpu.data.event import Event
    from predictionio_tpu.data.storage.base import App
    from predictionio_tpu.data.storage.registry import (
        SourceConfig,
        Storage,
        StorageConfig,
    )
    from predictionio_tpu.workflow.core import run_train
    from predictionio_tpu.workflow.server import (
        QueryServer,
        QueryServerConfig,
        latest_completed_runtime,
    )

    n_items_ur = 2_000 if SMALL else 100_000
    n_users_ur = 200 if SMALL else 3_000
    cfg = StorageConfig(
        sources={"MEM": SourceConfig("MEM", "memory", {})},
        repositories={
            "METADATA": "MEM", "EVENTDATA": "MEM", "MODELDATA": "MEM",
        },
    )
    storage = Storage(cfg)
    app_id = storage.get_meta_data_apps().insert(App(0, "urbench"))
    events = storage.get_events()
    events.init_app(app_id)
    rng = np.random.RandomState(13)
    batch: list[Event] = []
    for i in range(n_items_ur):  # full catalog coverage
        batch.append(Event(
            event="buy", entity_type="user",
            entity_id=f"u{int(rng.randint(n_users_ur))}",
            target_entity_type="item", target_entity_id=f"i{i}",
        ))
    for _ in range(n_users_ur * 30):
        batch.append(Event(
            event="buy", entity_type="user",
            entity_id=f"u{int(rng.randint(n_users_ur))}",
            target_entity_type="item",
            target_entity_id=f"i{int(rng.zipf(1.3)) % n_items_ur}",
        ))
    for lo in range(0, len(batch), 10_000):
        events.insert_batch(batch[lo:lo + 10_000], app_id)

    variant = {
        "id": "benchur",
        "engineFactory":
            "predictionio_tpu.engines.universal.UniversalRecommenderEngine",
        "datasource": {"params": {
            "app_name": "urbench", "indicators": ["buy"],
        }},
        "algorithms": [{
            "name": "ur",
            "params": {"app_name": "urbench", "indicators": ["buy"]},
        }],
    }
    run_train(storage, variant)
    runtime = latest_completed_runtime(storage, "benchur", "0", "benchur")
    srv = QueryServer(
        storage, runtime, QueryServerConfig(ip="127.0.0.1", port=0)
    )
    port = srv.start()
    try:
        # same client sweep as the ALS serving bench: 32 closed-loop
        # clients cap batches at 32 (measured ~110 qps at a 273 ms
        # device round trip); 64+ fill max_batch and should approach
        # the 64/0.273 ≈ 234 qps direct-path ceiling
        sweep = []
        for n_clients in (32, 64, 128):
            stats = _hammer_query_server(
                port,
                lambda i: json.dumps(
                    {
                        "user": f"u{i % n_users_ur}",
                        "num": 10,
                        "exclude_seen": True,
                    }
                ).encode(),
                n_clients=n_clients,
                n_per=6 if n_clients <= 64 else 4,
                timeout=120.0,
            )
            sweep.append(dict(stats, clients=n_clients))
        best = max(sweep, key=lambda r: r["qps"])
        return dict(best, catalog=n_items_ur, sweep=sweep)
    finally:
        srv.stop()


def bench_fleet():
    """Fleet scaling scenario (ISSUE 10): dense-ALS train throughput
    across 1/2/4/8 devices on the (dp, mp) mesh, plus a sharded-serving
    proof — a factor catalog deliberately sized OVER a single-device
    budget that `fleet.ShardedRuntime` serves with correct top-k.

    Runs in THIS process on the devices it can see (one process drives
    all the chips of a host; a child would need the chips this parent
    already holds). Device counts above what is visible are skipped,
    not simulated; with one device the serving proof is not run."""
    import jax

    from predictionio_tpu.fleet import (
        OversizedModelError,
        ShardedRuntime,
        check_single_device_budget,
        factor_state_bytes,
    )
    from predictionio_tpu.models import als
    from predictionio_tpu.parallel.mesh import MeshConf

    n_visible = len(jax.devices())
    n_users, n_items, n_edges = (
        (1024, 512, 30_000) if SMALL else (8192, 2048, 400_000)
    )
    train_iters = 2 if SMALL else 4

    def train_scaling_point(n: int) -> dict:
        rng = np.random.RandomState(0)
        keys = np.unique(
            rng.randint(0, n_users * n_items, n_edges).astype(np.int64)
        )
        rows = (keys // n_items).astype(np.int32)
        cols = (keys % n_items).astype(np.int32)
        vals = np.float32(1.0) + (keys % 5).astype(np.float32)
        p = als.ALSParams(rank=10, iterations=train_iters, cg_iterations=3)
        mp = 2 if n >= 2 else 1
        mesh = MeshConf(dp=-1, mp=mp, devices=n).build() if n > 1 else None
        staged = als.stage_dense(
            rows, cols, vals, n_users, n_items, p, mesh=mesh
        )
        uf, itf = staged.run()  # compile warmup
        np.asarray(uf[:1, :1])
        times = []
        for _ in range(3):
            t0 = time.perf_counter()
            uf, itf = staged.run()
            np.asarray(uf[:1, :1])  # sync fetch
            times.append(time.perf_counter() - t0)
        return {
            "devices": n, "mp": mp,
            "edges": int(len(keys)),
            "device_sec": min(times),
            "events_per_sec": len(keys) * train_iters / min(times),
        }

    def serve_proof() -> dict:
        n_u, n_i, rank = 20_000, 50_000, 32
        rng = np.random.RandomState(1)
        uf = rng.randn(n_u, rank).astype(np.float32)
        itf = rng.randn(n_i, rank).astype(np.float32)
        total = factor_state_bytes(n_u, n_i, rank)
        budget = total / 2  # one "chip" holds half of the catalog
        refused = False
        try:
            check_single_device_budget(n_u, n_i, rank, budget)
        except OversizedModelError:
            refused = True
        srt = ShardedRuntime(uf, itf, device_budget_bytes=budget)
        m = als.ALSFactors(uf, itf, None, None)
        q = rng.randint(0, n_u, 16).astype(np.int64)
        v0, i0 = als.recommend(m, q, 10)
        v1, i1 = srt.recommend(q, 10)
        ok = bool(np.allclose(v0, v1, rtol=1e-4) and (i0 == i1).all())
        times = []
        for _ in range(10):
            t0 = time.perf_counter()
            srt.recommend(q, 10)
            times.append(time.perf_counter() - t0)
        times.sort()
        return {
            "shards": srt.n_shards,
            "catalog_rows": n_u + n_i,
            "factor_bytes_total": total,
            "single_device_budget": budget,
            "single_device_refused": refused,
            "sharded_loads": True,
            "per_shard_bytes": srt.device_bytes()["per_shard"],
            "topk_matches_dense": ok,
            "recommend_p50_ms": times[len(times) // 2] * 1e3,
        }

    scaling = []
    for n in (1, 2, 4, 8):
        if n > n_visible:
            break
        res = train_scaling_point(n)
        if scaling:
            res["speedup_vs_1"] = round(
                res["events_per_sec"] / scaling[0]["events_per_sec"], 3
            )
        scaling.append(res)
    serve = serve_proof() if n_visible >= 4 else None
    return {"train_scaling": scaling, "serve_shards": serve}


def bench_sharded_ingestion():
    """Ingest scaling across storage shards (VERDICT r4 #6): the batch
    endpoint -> entity-hash routing -> per-shard bulk writes, measured
    against 1, 2 and 4 sqlite-backed storage DAEMONS (real processes,
    real RPC — the HBase distributed-write role, HBEventsUtil.scala:
    81-106). Near-linear scaling is the claim the sharded store makes."""
    import concurrent.futures
    import socket
    import subprocess
    import sys as _sys
    import tempfile
    import urllib.request

    from predictionio_tpu.data.api.server import (
        EventServer,
        EventServerConfig,
    )
    from predictionio_tpu.data.storage.base import AccessKey, App
    from predictionio_tpu.data.storage.registry import (
        SourceConfig,
        Storage,
        StorageConfig,
    )

    def free_port():
        sk = socket.socket()
        sk.bind(("127.0.0.1", 0))
        p = sk.getsockname()[1]
        sk.close()
        return p

    def _reap(children):
        for c in children:
            c.terminate()
        for c in children:
            try:
                c.wait(timeout=10)
            except subprocess.TimeoutExpired:
                c.kill()
                c.wait()

    rng = np.random.RandomState(5)
    batches_per, batch_size = 12 if SMALL else 60, 50

    def one_config(n_shards: int) -> dict:
        n_writers = 4 * n_shards  # keep every front end fed
        tmp = tempfile.mkdtemp(prefix=f"pio_shard_ingest{n_shards}_")
        procs, ports = [], []
        try:
            for tag in range(n_shards):
                port = free_port()
                ports.append(port)
                env = dict(os.environ)
                env.update({
                    "PIO_STORAGE_SOURCES_SQL_TYPE": "sqlite",
                    "PIO_STORAGE_SOURCES_SQL_PATH": f"{tmp}/s{tag}.db",
                    "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "SQL",
                    "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "SQL",
                    "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "SQL",
                })
                procs.append(subprocess.Popen(
                    [_sys.executable, "-m",
                     "predictionio_tpu.data.api.storage_server",
                     "--host", "127.0.0.1", "--port", str(port)],
                    env=env, stdout=subprocess.DEVNULL,
                    stderr=subprocess.DEVNULL,
                ))
            for port in ports:
                for _ in range(100):
                    try:
                        urllib.request.urlopen(
                            f"http://127.0.0.1:{port}/health", timeout=1
                        )
                        break
                    except Exception:
                        time.sleep(0.1)
            # metadata lives on daemon 0 so MULTIPLE event-server
            # processes share apps/keys — one front end saturates its
            # GIL near 9k ev/s, so horizontal ingest scale needs the
            # reference's shape: N event servers over the shared store
            shard_spec = ",".join(f"127.0.0.1:{p}" for p in ports)
            cfg = StorageConfig(
                sources={
                    "SH": SourceConfig("SH", "sharded", {
                        "SHARDS": shard_spec,
                    }),
                    "RM": SourceConfig("RM", "remote", {
                        "HOST": "127.0.0.1", "PORT": str(ports[0]),
                    }),
                },
                repositories={
                    "METADATA": "RM", "EVENTDATA": "SH",
                    "MODELDATA": "RM",
                },
            )
            storage = Storage(cfg)
            app_id = storage.get_meta_data_apps().insert(
                App(0, "shardingest")
            )
            storage.get_events().init_app(app_id)
            storage.get_meta_data_access_keys().insert(
                AccessKey(key="BENCHKEY", app_id=app_id, events=())
            )
            # one front end per shard WHEN the host has cores for them
            # — on a 1-2 core host extra fronts just thrash the
            # scheduler and the measurement reads as inverse scaling
            n_front = max(
                1, min(n_shards, (os.cpu_count() or 1) // 2)
            )
            fronts, fports = [], []
            fenv = dict(os.environ)
            fenv.update({
                "PIO_STORAGE_SOURCES_SH_TYPE": "sharded",
                "PIO_STORAGE_SOURCES_SH_SHARDS": shard_spec,
                "PIO_STORAGE_SOURCES_RM_TYPE": "remote",
                "PIO_STORAGE_SOURCES_RM_HOST": "127.0.0.1",
                "PIO_STORAGE_SOURCES_RM_PORT": str(ports[0]),
                "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "RM",
                "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "SH",
                "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "RM",
            })
            for _f in range(n_front):
                fp = free_port()
                fports.append(fp)
                fronts.append(subprocess.Popen(
                    [_sys.executable, "-m",
                     "predictionio_tpu.tools.console", "eventserver",
                     "--ip", "127.0.0.1", "--port", str(fp)],
                    env=fenv, stdout=subprocess.DEVNULL,
                    stderr=subprocess.DEVNULL,
                ))
            for fp in fports:
                for _ in range(150):
                    try:
                        urllib.request.urlopen(
                            f"http://127.0.0.1:{fp}/", timeout=1
                        )
                        break
                    except Exception:
                        time.sleep(0.1)

            def make_batch():
                return json.dumps([
                    {
                        "event": "rate",
                        "entityType": "user",
                        "entityId": f"u{int(rng.randint(50_000))}",
                        "targetEntityType": "item",
                        "targetEntityId": f"i{int(rng.randint(5_000))}",
                        "properties": {"rating": float(rng.randint(1, 6))},
                    }
                    for _ in range(batch_size)
                ]).encode()

            payloads = [
                [make_batch() for _ in range(batches_per)]
                for _ in range(n_writers)
            ]
            def writer(w):
                fp = fports[w % len(fports)]  # writers spread over fronts
                url = (
                    f"http://127.0.0.1:{fp}/batch/events.json"
                    f"?accessKey=BENCHKEY"
                )
                for body in payloads[w]:
                    req = urllib.request.Request(
                        url, data=body,
                        headers={"Content-Type": "application/json"},
                        method="POST",
                    )
                    with urllib.request.urlopen(req, timeout=120) as r:
                        r.read()

            try:
                writer(0)  # warm
                t0 = time.perf_counter()
                with concurrent.futures.ThreadPoolExecutor(
                    n_writers
                ) as pool:
                    list(pool.map(writer, range(n_writers)))
                wall = time.perf_counter() - t0
                return {
                    "events_per_sec":
                        n_writers * batches_per * batch_size / wall,
                    "front_ends": n_front,
                }
            finally:
                _reap(fronts)
        finally:
            _reap(procs)

    shard_counts = (1, 2) if SMALL else (1, 2, 4)
    # the scaling claim needs real cores: daemons + front ends + writers
    # all contend for CPU, so on a 1-2 core host more shards only add
    # context switching — record the host size so the ledger reads
    # honestly either way
    return {
        "host_cpus": os.cpu_count(),
        "per_shards": [
            {"shards": n, **one_config(n)} for n in shard_counts
        ],
    }


def bench_gateway():
    """ISSUE 15 (BENCH_r09): the replicated serving tier. Three stub
    replica subprocesses (echo engine, deterministic 2% stragglers)
    behind an in-process gateway over shared sqlite:

    - routing overhead: through-gateway p50 minus direct-to-replica
      p50 on the SAME single-client loop, plus the gateway's own
      routing-decision histogram,
    - hedged vs unhedged p99 under a concurrent hammer against the
      straggler tail (hedging OFF first so the tail is measured, then
      ON — `gateway_hedged_p99_ratio` < 1 is the win),
    - zero-drop failover: kill -9 one replica mid-hammer and count
      in-deadline failures (`gateway_failover_dropped`, bar: 0),
    - deadline honesty: every hedge carries the REMAINING budget, so
      the replicas' deadline-shed counters record any post-deadline
      work the gateway dispatched (`gateway_post_deadline_work`,
      bar: 0 — hedging must never exceed the budget).

    Stub replicas mean no jax and no training: the numbers isolate the
    GATEWAY's added cost and its availability math, which is exactly
    what this tier contributes."""
    import shutil
    import signal as _signal
    import socket as _socket
    import subprocess
    import sys as _sys
    import tempfile
    import threading
    import urllib.request as _rq

    from predictionio_tpu.data.storage.registry import (
        SourceConfig,
        Storage,
        StorageConfig,
    )
    from predictionio_tpu.gateway import GatewayConfig, GatewayServer

    tmp = tempfile.mkdtemp(prefix="bench-gateway-")
    db = os.path.join(tmp, "gateway.db")
    storage = Storage(StorageConfig(
        sources={"SQL": SourceConfig("SQL", "sqlite", {"PATH": db})},
        repositories={
            "METADATA": "SQL", "EVENTDATA": "SQL", "MODELDATA": "SQL",
        },
    ))

    def free_port() -> int:
        with _socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    def spawn(rid: str, port: int) -> subprocess.Popen:
        env = dict(os.environ)
        env.update({
            "PIO_STORAGE_SOURCES_SQL_TYPE": "sqlite",
            "PIO_STORAGE_SOURCES_SQL_PATH": db,
            "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "SQL",
            "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "SQL",
            "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "SQL",
            "PIO_REPLICA_HEARTBEAT_S": "0.2",
            "JAX_PLATFORMS": "cpu",
        })
        return subprocess.Popen(
            [_sys.executable, "-m",
             "predictionio_tpu.gateway.replica_main",
             "--stub", "--ip", "127.0.0.1", "--port", str(port),
             "--replica-id", rid,
             "--state-dir", os.path.join(tmp, f"state-{rid}"),
             # every 50th query sleeps 200 ms: a 2% straggler tail, so
             # the rolling p95 hedge trigger stays FAST (stragglers
             # are beyond it) while p99 sits on the tail — the shape
             # hedging is built for. A tail rate at/above 5% would
             # push p95 onto the straggler itself and the hedge would
             # rightly fire too late to help.
             "--slow-every", "50", "--slow-ms", "200"],
            env=env,
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
        )

    ports = {f"r{i}": free_port() for i in range(3)}
    procs = {rid: spawn(rid, port) for rid, port in ports.items()}
    gw = GatewayServer(storage, GatewayConfig(
        ip="127.0.0.1", port=0, sync_interval_s=0.15,
        replica_stale_after_s=1.5, scrape=False,
        hedge=False,  # phase-controlled below
        hedge_min_ms=40.0, breaker_threshold=2, breaker_cooldown_s=0.5,
    ))
    gport = gw.start()

    def post(port, body, deadline_ms=8000, timeout=15):
        req = _rq.Request(
            f"http://127.0.0.1:{port}/queries.json",
            data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json",
                     "X-PIO-Deadline": str(deadline_ms)},
            method="POST",
        )
        with _rq.urlopen(req, timeout=timeout) as r:
            return json.loads(r.read().decode())

    def loop_p50(port, n, tag):
        times = []
        for i in range(n):
            t0 = time.perf_counter()
            post(port, {"q": f"{tag}-{i}"})
            times.append(time.perf_counter() - t0)
        return float(np.percentile(times, 50)) * 1e3

    def hammer(n_clients, per_client, tag, deadline_ms=8000):
        times: list[float] = []
        failed: list[str] = []
        lock = threading.Lock()

        def run(c):
            for i in range(per_client):
                t0 = time.perf_counter()
                try:
                    post(gport, {"q": f"{tag}-{c}-{i}"},
                         deadline_ms=deadline_ms)
                    dt = time.perf_counter() - t0
                    with lock:
                        times.append(dt)
                except Exception as e:
                    with lock:
                        failed.append(str(e))

        threads = [
            threading.Thread(target=run, args=(c,), daemon=True)
            for c in range(n_clients)
        ]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        wall = time.perf_counter() - t0
        return times, failed, wall

    def replica_shed_total() -> float:
        total = 0.0
        from predictionio_tpu.obs.monitor import parse_prometheus_text

        for rid, port in ports.items():
            if procs.get(rid) is None or procs[rid].poll() is not None:
                continue
            try:
                with _rq.urlopen(
                    f"http://127.0.0.1:{port}/metrics", timeout=5
                ) as r:
                    body = r.read().decode(errors="replace")
            except OSError:
                continue
            for name, labels, value in parse_prometheus_text(body):
                if (
                    name == "queries_shed_total"
                    and labels.get("reason") == "deadline"
                ):
                    total += value
        return total

    out: dict = {"replicas": 3}
    try:
        # wait for discovery
        deadline = time.time() + 30
        while time.time() < deadline:
            gw.sync_once()
            _ring, states = gw._route_snapshot()
            if sum(1 for st in states.values() if st.routable()) >= 3:
                break
            time.sleep(0.2)

        n_probe = 60 if SMALL else 200
        # warm both paths (keep-alives, straggler counters past 0)
        loop_p50(ports["r0"], 25, "warm-direct")
        loop_p50(gport, 25, "warm-gw")
        direct_p50 = loop_p50(ports["r0"], n_probe, "direct")
        via_p50 = loop_p50(gport, n_probe, "via")
        out["gateway_direct_p50_ms"] = round(direct_p50, 3)
        out["gateway_via_p50_ms"] = round(via_p50, 3)
        out["gateway_routing_overhead_p50_ms"] = round(
            max(0.0, via_p50 - direct_p50), 3
        )
        out["gateway_routing_decision_p50_ms"] = round(
            gw._routing_hist.quantile(0.5) * 1e3, 4
        )

        # hedged-vs-unhedged p99 against the 2% straggler tail
        n_clients = 8 if SMALL else 16
        per_client = 30 if SMALL else 60
        gw.config.hedge = False
        unhedged, failed_u, _ = hammer(n_clients, per_client, "unhedged")
        gw.config.hedge = True
        hedged, failed_h, wall_h = hammer(n_clients, per_client, "hedged")
        unhedged_p99 = float(np.percentile(unhedged, 99)) * 1e3
        hedged_p99 = float(np.percentile(hedged, 99)) * 1e3
        out["gateway_unhedged_p99_ms"] = round(unhedged_p99, 2)
        out["gateway_hedged_p99_ms"] = round(hedged_p99, 2)
        out["gateway_hedged_p99_ratio"] = round(
            hedged_p99 / unhedged_p99, 3
        ) if unhedged_p99 > 0 else None
        out["gateway_hedges_sent"] = int(gw._hedges.value(outcome="sent"))
        out["gateway_hedges_won"] = int(gw._hedges.value(outcome="won"))
        out["gateway_hedge_phase_qps"] = round(
            len(hedged) / wall_h, 1
        ) if wall_h > 0 else None
        # deadline honesty: the replicas' own deadline-shed counters
        # record any gateway dispatch that arrived past its budget
        out["gateway_post_deadline_work"] = replica_shed_total()
        out["gateway_hedge_failed"] = len(failed_u) + len(failed_h)

        # zero-drop failover: kill -9 one replica mid-hammer (the
        # hammer is sized to straddle the kill AND the ejection window,
        # so post-kill queries actually exercise failover)
        dropped: list[str] = []
        times_k: list[float] = []
        per_failover = 150 if SMALL else 300

        def kill_later():
            time.sleep(0.4)
            victim = procs.pop("r2")
            victim.send_signal(_signal.SIGKILL)
            victim.wait(timeout=10)

        killer = threading.Thread(target=kill_later, daemon=True)
        killer.start()
        times_k, dropped, _ = hammer(
            n_clients, per_failover, "failover"
        )
        killer.join(timeout=20)
        out["gateway_failover_dropped"] = len(dropped)
        out["gateway_failover_total"] = int(gw._failovers.value())
        out["gateway_failover_p99_ms"] = round(
            float(np.percentile(times_k, 99)) * 1e3, 2
        ) if times_k else None
        out["host_cpus"] = os.cpu_count()
        out["note"] = (
            "stub replicas (echo engine, 2% 200 ms stragglers): the "
            "numbers isolate gateway-added routing/hedging/failover "
            "cost from model compute"
        )
    finally:
        gw.stop()
        for proc in procs.values():
            try:
                proc.kill()
                proc.wait(timeout=10)
            except Exception:
                pass
        shutil.rmtree(tmp, ignore_errors=True)
    return out


def bench_fleetobs():
    """ISSUE 16 (BENCH_r10): the fleet observability plane.

    - SLO evaluation with recording rules: the engine's recorded fast
      path (read one precomputed slo_error_ratio point per window)
      versus the raw rescan (re-walk every matching 720-point ring) on
      the SAME fleet-shaped TSDB — `fleetobs_slo_eval_ratio` ≤ 0.5 is
      the bar,
    - gateway routing p50 with the WHOLE plane attached (request
      tracing, /metrics scraping, the cross-process trace collector
      polling every replica): `fleetobs_gateway_via_p50_ms` must stay
      within 1.15× of BENCH_r09's untraced gateway_via_p50_ms.
    """
    import shutil
    import socket as _socket
    import subprocess
    import sys as _sys
    import tempfile
    import urllib.request as _rq

    from predictionio_tpu.obs.monitor.slo import (
        SLOEngine,
        SLOSpec,
        record_slo_ratios,
    )
    from predictionio_tpu.obs.monitor.tsdb import TSDB
    from predictionio_tpu.obs.registry import MetricsRegistry

    out: dict = {}

    # -- SLO eval: recorded fast path vs raw rescan at full rings ----------
    db = TSDB(capacity=720)
    now = time.time()
    instances = ("r0", "r1", "r2")
    # full 720-point rings per series — the steady-state shape after
    # one TSDB retention period of scraping a 3-replica fleet
    for i in range(720):
        t = now - (719 - i)
        for inst in instances:
            for status, v in (("200", 100.0 * i), ("500", 1.0 * i)):
                db.add(
                    "http_requests_total",
                    {"server": "query", "path": "/queries.json",
                     "status": status, "instance": inst},
                    v, "counter", t,
                )
            db.add("up", {"instance": inst}, 1.0, "gauge", t)
    specs = [
        SLOSpec(name="avail-sum", kind="availability", objective=0.9,
                aggregate="sum", min_samples=1),
        SLOSpec(name="avail-mean", kind="availability", objective=0.9,
                aggregate="mean", min_samples=1),
        SLOSpec(name="fleet-up", kind="up", objective=0.9,
                aggregate="mean", min_samples=1),
        SLOSpec(name="avail-local", kind="availability", objective=0.9,
                min_samples=1),
    ]
    iters = 30 if SMALL else 100

    def eval_ms(engine) -> float:
        times = []
        for _ in range(iters):
            t0 = time.perf_counter()
            engine.evaluate_once(now=now)
            times.append(time.perf_counter() - t0)
        return float(np.percentile(times, 50)) * 1e3

    engine = SLOEngine(db, specs, registry=MetricsRegistry())
    engine.recorded_max_age_s = 0.0  # raw rescan only
    raw_ms = eval_ms(engine)
    t0 = time.perf_counter()
    recorded_points = record_slo_ratios(db, specs, now=now)
    recording_pass_ms = (time.perf_counter() - t0) * 1e3
    engine.recorded_max_age_s = 3600.0  # fast path always fresh
    recorded_ms = eval_ms(engine)
    out["fleetobs_slo_specs"] = len(specs)
    out["fleetobs_slo_eval_raw_ms"] = round(raw_ms, 4)
    out["fleetobs_slo_eval_recorded_ms"] = round(recorded_ms, 4)
    out["fleetobs_slo_eval_ratio"] = round(
        recorded_ms / raw_ms, 4
    ) if raw_ms > 0 else None
    out["fleetobs_recording_pass_ms"] = round(recording_pass_ms, 4)
    out["fleetobs_recording_points"] = recorded_points

    # -- gateway p50 with tracing + collector attached ---------------------
    from predictionio_tpu.data.storage.registry import (
        SourceConfig,
        Storage,
        StorageConfig,
    )
    from predictionio_tpu.gateway import GatewayConfig, GatewayServer

    tmp = tempfile.mkdtemp(prefix="bench-fleetobs-")
    dbfile = os.path.join(tmp, "gateway.db")
    storage = Storage(StorageConfig(
        sources={"SQL": SourceConfig("SQL", "sqlite", {"PATH": dbfile})},
        repositories={
            "METADATA": "SQL", "EVENTDATA": "SQL", "MODELDATA": "SQL",
        },
    ))

    def free_port() -> int:
        with _socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    def spawn(rid: str, port: int) -> subprocess.Popen:
        env = dict(os.environ)
        env.update({
            "PIO_STORAGE_SOURCES_SQL_TYPE": "sqlite",
            "PIO_STORAGE_SOURCES_SQL_PATH": dbfile,
            "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "SQL",
            "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "SQL",
            "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "SQL",
            "PIO_REPLICA_HEARTBEAT_S": "0.2",
            "JAX_PLATFORMS": "cpu",
        })
        return subprocess.Popen(
            [_sys.executable, "-m",
             "predictionio_tpu.gateway.replica_main",
             "--stub", "--ip", "127.0.0.1", "--port", str(port),
             "--replica-id", rid,
             "--state-dir", os.path.join(tmp, f"state-{rid}"),
             # same 2% straggler tail as BENCH_r09, so the p50s compare
             "--slow-every", "50", "--slow-ms", "200"],
            env=env,
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
        )

    ports = {f"r{i}": free_port() for i in range(3)}
    procs = {rid: spawn(rid, port) for rid, port in ports.items()}
    old_collect = os.environ.get("PIO_TRACE_COLLECT")
    os.environ["PIO_TRACE_COLLECT"] = "1"
    gw = GatewayServer(storage, GatewayConfig(
        ip="127.0.0.1", port=0, sync_interval_s=0.15,
        replica_stale_after_s=1.5,
        scrape=True, scrape_interval_s=0.5,  # plane ON (unlike r09)
        hedge=True, hedge_min_ms=40.0,
        breaker_threshold=2, breaker_cooldown_s=0.5,
    ))
    gport = gw.start()

    def post(port, body):
        req = _rq.Request(
            f"http://127.0.0.1:{port}/queries.json",
            data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json",
                     "X-PIO-Deadline": "8000"},
            method="POST",
        )
        with _rq.urlopen(req, timeout=15) as r:
            return json.loads(r.read().decode())

    def loop_p50(port, n, tag):
        times = []
        for i in range(n):
            t0 = time.perf_counter()
            post(port, {"q": f"{tag}-{i}"})
            times.append(time.perf_counter() - t0)
        return float(np.percentile(times, 50)) * 1e3

    try:
        deadline = time.time() + 30
        while time.time() < deadline:
            gw.sync_once()
            _ring, states = gw._route_snapshot()
            if sum(1 for st in states.values() if st.routable()) >= 3:
                break
            time.sleep(0.2)
        n_probe = 60 if SMALL else 200
        loop_p50(ports["r0"], 25, "warm-direct")
        loop_p50(gport, 25, "warm-gw")
        direct_p50 = loop_p50(ports["r0"], n_probe, "direct")
        via_p50 = loop_p50(gport, n_probe, "via")
        out["fleetobs_gateway_direct_p50_ms"] = round(direct_p50, 3)
        out["fleetobs_gateway_via_p50_ms"] = round(via_p50, 3)
        out["fleetobs_gateway_overhead_p50_ms"] = round(
            max(0.0, via_p50 - direct_p50), 3
        )
        from predictionio_tpu.obs.monitor import get_monitor

        col = get_monitor().collector
        if col is not None:
            # let the collector drain its last poll cycle, then prove
            # the plane actually ran during the measurement
            time.sleep(1.0)
            col.collect_once()
            st = col.status()
            out["fleetobs_traces_assembled"] = st["assembled"]
            out["fleetobs_collector_polls"] = st["polls"]
        try:
            with open(os.path.join(
                os.path.dirname(os.path.abspath(__file__)),
                "BENCH_r09.json",
            )) as f:
                r09_p50 = float(json.load(f)["gateway_via_p50_ms"])
            out["fleetobs_gateway_p50_vs_r09"] = round(
                via_p50 / r09_p50, 3
            )
        except (OSError, KeyError, ValueError):
            out["fleetobs_gateway_p50_vs_r09"] = None
        out["host_cpus"] = os.cpu_count()
        out["note"] = (
            "same stub-replica harness as BENCH_r09 with the whole "
            "observability plane attached (tracing, scraping, trace "
            "collector); fleetobs_gateway_p50_vs_r09 is the tax"
        )
    finally:
        gw.stop()
        if old_collect is None:
            os.environ.pop("PIO_TRACE_COLLECT", None)
        else:
            os.environ["PIO_TRACE_COLLECT"] = old_collect
        for proc in procs.values():
            try:
                proc.kill()
                proc.wait(timeout=10)
            except Exception:
                pass
        shutil.rmtree(tmp, ignore_errors=True)
    return out


def bench_push_telemetry():
    """ISSUE 17 (BENCH_r11): the push half of the telemetry plane.

    - serving p99 with a TelemetryShipper attached to the serving
      process (spooling + POSTing to the SAME server the requests hit)
      versus detached — `push_attach_p99_ratio` < 1.05 is the bar,
    - spool→queryable latency: a marker series spooled to disk, shipped
      through POST /telemetry/push, polled out of the fleet TSDB,
    - expression eval p50 over a fleet-shaped TSDB (the recording-rule
      tick cost of a cross-family `sum by (instance)` ratio).
    """
    import shutil
    import tempfile
    import threading
    import urllib.request as _rq

    from predictionio_tpu.obs.monitor import get_monitor
    from predictionio_tpu.obs.monitor import push as _push
    from predictionio_tpu.obs.monitor.expr import evaluate_rows
    from predictionio_tpu.obs.monitor.tsdb import TSDB
    from predictionio_tpu.obs.registry import MetricsRegistry
    from predictionio_tpu.utils.http import (
        HttpError,
        JsonHandler,
        ThreadedServer,
    )

    out: dict = {}

    from predictionio_tpu.obs.spans import SpanRecorder as _Rec

    class _PushHandler(JsonHandler):
        def do_GET(self):
            self._drain_body()
            try:
                if self.path.split("?")[0].rstrip("/") == "/metrics":
                    self._serve_metrics()
                else:
                    raise HttpError(404, "Not Found")
            except HttpError as e:
                self._respond(e.status, {"message": e.message})

        def do_POST(self):
            self._drain_body()
            try:
                if self.path.split("?")[0].rstrip("/") == "/telemetry/push":
                    self._serve_telemetry_push()
                else:
                    raise HttpError(404, "Not Found")
            except HttpError as e:
                self._respond(e.status, {"message": e.message})

    tmp = tempfile.mkdtemp(prefix="bench-push-")
    old_ingest = os.environ.get("PIO_PUSH_INGEST")
    os.environ["PIO_PUSH_INGEST"] = "1"
    srv = ThreadedServer(("127.0.0.1", 0), _PushHandler)
    port = srv.server_address[1]
    srv_thread = threading.Thread(target=srv.serve_forever, daemon=True)
    srv_thread.start()
    base = f"http://127.0.0.1:{port}"

    def loop_p99(n: int) -> float:
        times = []
        for _ in range(n):
            t0 = time.perf_counter()
            with _rq.urlopen(base + "/metrics", timeout=10) as r:
                r.read()
            times.append(time.perf_counter() - t0)
        return float(np.percentile(times, 99)) * 1e3

    try:
        n_probe = 2000 if SMALL else 4000
        rounds = 5  # interleaved A/B rounds; median-of-round-p99 is
        # the statistic (single-pool p99 swings ~±40% between phases
        # on shared CI cores even with NO shipper — measured). Each
        # round spans several seconds so a round CONTAINS whole push
        # passes at the production cadence, instead of compressing
        # pushes to a 20x-production duty cycle.
        reg = MetricsRegistry()
        hist = reg.histogram(
            "bench_serving_seconds", "synthetic serving latency",
        )
        loop_p99(30)  # warm the connection path
        detached_p99s, attached_p99s = [], []
        shipper = _push.TelemetryShipper(
            spool_dir=os.path.join(tmp, "spool"),
            url=base,
            instance="bench-serving",
            # a serving replica's spans reach the collector via the
            # POLL path (/debug/traces); its shipper covers the metric
            # families — so don't let the bench's own server.request
            # span firehose (one per loop request, default recorder)
            # masquerade as push volume
            recorder=_Rec(),
            interval_s=None,  # the production default cadence (10 s):
            # the question is "what does the shipper cost a serving
            # process AS CONFIGURED", not under an artificial hot loop
            registries=[reg],
        )
        def attached_round() -> float:
            shipper.start()
            try:
                times = []
                for i in range(n_probe):
                    t0 = time.perf_counter()
                    with _rq.urlopen(base + "/metrics", timeout=10) as r:
                        r.read()
                    dt = time.perf_counter() - t0
                    times.append(dt)
                    hist.observe(dt)  # real data for the snapshots
                return float(np.percentile(times, 99)) * 1e3
            finally:
                shipper.stop()  # joins + flush; restartable

        for r_i in range(rounds):
            # alternate phase order so a monotone machine-load drift
            # can't masquerade as attach overhead
            if r_i % 2 == 0:
                detached_p99s.append(loop_p99(n_probe))
                attached_p99s.append(attached_round())
            else:
                attached_p99s.append(attached_round())
                detached_p99s.append(loop_p99(n_probe))
        shipped_total = shipper.shipped
        detached_p99 = float(np.median(detached_p99s))
        attached_p99 = float(np.median(attached_p99s))
        out["push_attach_p99_detached_ms"] = round(detached_p99, 4)
        out["push_attach_p99_attached_ms"] = round(attached_p99, 4)
        out["push_attach_p99_ratio"] = round(
            attached_p99 / detached_p99, 4
        ) if detached_p99 > 0 else None
        out["push_batches_shipped"] = shipped_total

        # -- spool → queryable latency --------------------------------------
        marker = {
            "v": _push.PAYLOAD_VERSION,
            "instance": "bench-spool",
            "sampled_at": time.time(),
            "series": [{
                "name": "bench_push_marker", "labels": {},
                "value": 1.0, "kind": "gauge",
            }],
            "spans": [],
        }
        spool2 = os.path.join(tmp, "spool2")
        t0 = time.perf_counter()
        _push.spool_payload(spool2, marker)
        _push.ship_spool(spool2, base)
        tsdb = get_monitor().tsdb
        deadline = time.time() + 10
        while time.time() < deadline:
            if tsdb.matching(
                "bench_push_marker", {"instance": "bench-spool"}
            ):
                break
            time.sleep(0.001)
        else:
            raise RuntimeError("pushed marker never became queryable")
        out["push_spool_to_query_ms"] = round(
            (time.perf_counter() - t0) * 1e3, 4
        )
    finally:
        srv.shutdown()
        srv.server_close()
        srv_thread.join(timeout=10)
        if old_ingest is None:
            os.environ.pop("PIO_PUSH_INGEST", None)
        else:
            os.environ["PIO_PUSH_INGEST"] = old_ingest
        shutil.rmtree(tmp, ignore_errors=True)

    # -- expression eval p50 over a fleet-shaped TSDB ----------------------
    db = TSDB(capacity=720)
    now = time.time()
    for i in range(720):
        t = now - (719 - i)
        for inst in ("r0", "r1", "r2"):
            db.add("errors_total", {"instance": inst, "route": "/q"},
                   1.0 * i, "counter", t)
            db.add("requests_total", {"instance": inst, "route": "/q"},
                   100.0 * i, "counter", t)
    expr = ("sum by (instance) (increase(errors_total[5m])) / "
            "sum by (instance) (increase(requests_total[5m]))")
    iters = 30 if SMALL else 100
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        rows = evaluate_rows(db, expr, now=now)
        times.append(time.perf_counter() - t0)
    assert len(rows) == 3, rows
    out["push_expr_eval_p50_ms"] = round(
        float(np.percentile(times, 50)) * 1e3, 4
    )
    out["push_expr_series_scanned"] = db.series_count()
    out["host_cpus"] = os.cpu_count()
    out["note"] = (
        "shipper attached to the serving process, POSTing to the same "
        "server the p99 loop hits; spool→query includes fsync, HTTP "
        "ship, ingest, and TSDB visibility"
    )
    return out


def bench_durable_tsdb():
    """ISSUE 18 (BENCH_r12): the durable long-horizon TSDB tier.

    - WAL flush throughput (points/s through add + fsync'd flush_once),
    - replay latency: a cold DurableTSDB reconstructing its ring from
      WAL + sealed blocks,
    - one forced compaction pass (raw → 5m → 1h) over ~3 days of data,
    - the acceptance query: increase() over a 3-day window answered
      from the downsampled tiers — p50 must be far under 100ms,
    - downsample agreement: the same in-retention window answered from
      raw blocks vs 5m buckets (relative error within the documented
      edge-bucket bound).
    """
    import shutil
    import tempfile

    from predictionio_tpu.obs.monitor.compact import Compactor
    from predictionio_tpu.obs.monitor.durable import DurableTSDB

    out: dict = {}
    tmp = tempfile.mkdtemp(prefix="bench-dtsdb-")
    try:
        db = DurableTSDB(
            os.path.join(tmp, "tsdb"), capacity=720,
            flush_interval_s=9999.0, seal_age_s=9999.0,
        )
        now = time.time()
        start = now - 3 * 86400
        step = 120.0 if SMALL else 60.0
        series = 2 if SMALL else 4
        t0 = time.perf_counter()
        n_pts = 0
        for i in range(series):
            v = 0.0
            t = start
            while t <= now:
                v += 5.0
                db.add("bench_reqs_total", {"inst": f"r{i}"}, v,
                       "counter", t)
                t += step
                n_pts += 1
        db.flush_once(seal=True)
        wall = time.perf_counter() - t0
        out["tsdb_durable_flush_points_per_s"] = round(n_pts / wall)
        db.stop()

        # cold replay: the restart path every monitor pays on attach
        t0 = time.perf_counter()
        db = DurableTSDB(
            os.path.join(tmp, "tsdb"), capacity=720,
            flush_interval_s=9999.0, seal_age_s=9999.0,
        )
        out["tsdb_durable_replay_ms"] = round(
            (time.perf_counter() - t0) * 1e3, 3
        )
        assert db.replayed_points > 0

        comp = Compactor(db, interval_s=9999.0)
        t0 = time.perf_counter()
        comp.run_once(now=now, force=True)
        out["tsdb_durable_compact_ms"] = round(
            (time.perf_counter() - t0) * 1e3, 3
        )

        # downsample agreement BEFORE measuring the 3-day query (a
        # second retention pass may prune rolled-up raw blocks): the
        # same 4h window from raw points vs 5m buckets
        key = ("bench_reqs_total", (("inst", "r0"),))
        window = 4 * 3600.0
        raw_inc, _ = db._disk_increase(
            key, now - window, now, window, tier="raw"
        )
        ds_inc, _ = db._disk_increase(
            key, now - window, now, window, tier="5m"
        )
        out["tsdb_durable_downsample_rel_err"] = round(
            abs(ds_inc - raw_inc) / max(raw_inc, 1e-9), 6
        )

        s = db.matching("bench_reqs_total", {"inst": "r0"})[0]
        iters = 20 if SMALL else 50
        times = []
        for _ in range(iters):
            t0 = time.perf_counter()
            inc = db.series_increase(s, 3 * 86400.0, now)
            times.append(time.perf_counter() - t0)
        assert inc > 0
        out["tsdb_durable_query_3d_p50_ms"] = round(
            float(np.percentile(times, 50)) * 1e3, 4
        )
        tiers = db.durable_stats()["tiers"]
        out["tsdb_durable_disk_bytes"] = sum(
            st["bytes"] for st in tiers.values()
        )
        out["tsdb_durable_blocks"] = {
            t: st["blocks"] for t, st in tiers.items()
        }
        db.stop()
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    out["host_cpus"] = os.cpu_count()
    out["note"] = (
        "3 days of counters through WAL flush + seal + forced raw→5m→1h "
        "compaction; the 3-day increase() answers from the 1h tier"
    )
    return out


def bench_replication():
    """ISSUE 19 (BENCH_r13): the replicated event store.

    - acked ingest: insert_batch against a primary whose commit hook
      ships each WAL frame synchronously to one HTTP follower at
      min_acks=1 (every batch blocks on the follower's fsync + ack),
    - cold catch-up: ship throughput for a fresh replica pulling the
      sealed segments + WAL tail from scratch over the daemon RPC,
    - the acceptance ratio ship/ingest — must stay >= 0.5 or a cold
      follower can never catch a sustained ingest,
    - promotion-to-first-serve p50: elect_and_promote through the CAS
      election records to the first accepted write on the winner.
    """
    import shutil
    import tempfile

    from predictionio_tpu.data.api.storage_server import StorageServer
    from predictionio_tpu.data.event import Event
    from predictionio_tpu.data.storage.registry import (
        SourceConfig, Storage, StorageConfig,
    )
    from predictionio_tpu.data.storage.replication import (
        ReplicationConfig, SegmentShipper, elect_and_promote,
    )
    from predictionio_tpu.data.storage.segmentfs import (
        SegmentFSEventStore,
    )
    from predictionio_tpu.deploy.registry import LifecycleRecordStore
    from predictionio_tpu.obs.registry import MetricsRegistry

    app = 1
    out: dict = {}
    tmp = tempfile.mkdtemp(prefix="bench-repl-")
    daemons = []

    def _follower(name):
        storage = Storage(StorageConfig(
            sources={
                "REP": SourceConfig("REP", "segmentfs-replica", {
                    "PATH": os.path.join(tmp, name),
                    "SEAL_INTERVAL_S": "3600",
                }),
                "M": SourceConfig("M", "memory", {}),
            },
            repositories={
                "METADATA": "M", "EVENTDATA": "REP", "MODELDATA": "M",
            },
        ))
        daemon = StorageServer(storage, host="127.0.0.1", port=0).start()
        daemons.append(daemon)
        replica = storage.get_events()
        replica.init_app(app)
        return daemon, replica

    def _events(lo, hi):
        return [
            Event(
                event="rate", entity_type="user", entity_id=f"u{k}",
                target_entity_type="item",
                target_entity_id=f"i{k % 97}",
                properties={"rating": float(k % 5 + 1)},
            )
            for k in range(lo, hi)
        ]

    try:
        primary = SegmentFSEventStore({
            "PATH": os.path.join(tmp, "primary"),
            "SEAL_INTERVAL_S": "3600", "SEAL_AGE_S": "3600",
            "SEAL_EVENTS": "2000",
            "METRICS_REGISTRY": MetricsRegistry(),
        })
        primary.init_app(app)

        # acked ingest: the commit hook blocks each batch on the live
        # follower's WAL-frame ack (min_acks=1) — this is the write
        # path a production primary pays
        daemon_a, _replica_a = _follower("replica-a")
        shipper = SegmentShipper(
            primary,
            ReplicationConfig(
                followers=(f"127.0.0.1:{daemon_a.port}",),
                min_acks=1, ship_interval_s=9999.0, timeout_s=10.0,
            ),
            epoch=1, metrics=MetricsRegistry(),
        )
        n = 2_000 if SMALL else 8_000
        batch = 64
        evs = _events(0, n)
        t0 = time.perf_counter()
        for i in range(0, n, batch):
            primary.insert_batch(evs[i:i + batch], app)
        ingest_wall = time.perf_counter() - t0
        out["replication_ingest_eps"] = round(n / ingest_wall)
        primary.seal(app)
        shipper.pass_once()

        # cold catch-up: a fresh replica pulls every sealed segment +
        # the WAL tail from scratch through the daemon transport
        daemon_b, replica_b = _follower("replica-b")
        catchup = SegmentShipper(
            primary,
            ReplicationConfig(
                followers=(f"127.0.0.1:{daemon_b.port}",),
                timeout_s=10.0,
            ),
            epoch=1, metrics=MetricsRegistry(),
        )
        t0 = time.perf_counter()
        while len(replica_b.find_since(app, 0)) < n:
            catchup.pass_once()
        ship_wall = time.perf_counter() - t0
        out["replication_ship_eps"] = round(n / ship_wall)
        out["replication_ship_vs_ingest"] = round(
            out["replication_ship_eps"]
            / max(out["replication_ingest_eps"], 1), 2
        )
        assert replica_b.replication_lag(app)["lag"] == 0

        # promotion-to-first-serve: fenced CAS election through the
        # record store, then the first accepted write on the winner
        records = LifecycleRecordStore(Storage(StorageConfig(
            sources={"M": SourceConfig("M", "memory", {})},
            repositories={
                "METADATA": "M", "EVENTDATA": "M", "MODELDATA": "M",
            },
        )))
        rounds = 7 if SMALL else 15
        times = []
        for i in range(rounds):
            t0 = time.perf_counter()
            epoch = elect_and_promote(
                records, replica_b, f"bench-replica-{i}",
                group=f"bench-events-primary-{i}",
            )
            replica_b.insert_batch(_events(n + i, n + i + 1), app)
            times.append(time.perf_counter() - t0)
            assert epoch is not None
        out["replication_promotion_p50_ms"] = round(
            float(np.percentile(times, 50)) * 1e3, 3
        )
        out["replication_events"] = n
        shipper.stop()
        catchup.stop()
        primary.close()
    finally:
        for daemon in daemons:
            daemon.shutdown()
        shutil.rmtree(tmp, ignore_errors=True)
    out["host_cpus"] = os.cpu_count()
    out["note"] = (
        "one HTTP follower, min_acks=1 on the ingest loop (each batch "
        "blocks on the follower ack); catch-up ships sealed segments + "
        "WAL tail to a cold replica; promotion p50 spans CAS claim, "
        "promote(), and the first accepted write"
    )
    return out


def bench_eval_fleet():
    """ISSUE 20 (BENCH_r14): fleet-scale evaluation & auto-tuning.

    - fleet fan-out vs sequential: the same grid-compatible param space
      through `pio eval run` machinery (EvalDriver fan-out → per-fold
      shard jobs on a 2-worker fleet → durable partial records → fold)
      against the sequential MetricEvaluator on identical splits; the
      ratio must stay > 1 (fan-out beats one process) or the fleet is
      pure overhead,
    - grid-kernel grouping: batch_eval over the compatible group (ONE
      train_grid program per fold) vs the solo per-point path, plus the
      one-program assertion (every prediction stamped with the full
      group size — the compile-cache evidence that N points shared one
      device program),
    - records-fold overhead: a full `pio eval status` recompute (job
      states + per-point partial fold) on the finished run.

    The engine's train cost is a calibrated sleep (sample_engine grid
    engine): the bench measures ORCHESTRATION — fan-out, claim, shard,
    record, fold — not kernel arithmetic, which BENCH_r01..r08 cover.
    """
    import shutil
    import sys as _sys
    import tempfile

    repo_dir = os.path.dirname(os.path.abspath(__file__))
    tests_dir = os.path.join(repo_dir, "tests")
    if tests_dir not in _sys.path:
        _sys.path.insert(0, tests_dir)
    import sample_engine
    from predictionio_tpu.controller.evaluation import MetricEvaluator
    from predictionio_tpu.core.base import RuntimeContext, WorkflowParams
    from predictionio_tpu.data.storage.registry import (
        SourceConfig, Storage, StorageConfig,
    )
    from predictionio_tpu.deploy.scheduler import SchedulerConfig
    from predictionio_tpu.evalfleet import (
        EvalDriver, EvalDriverConfig, EvalSpec, expand_points,
    )
    from predictionio_tpu.evalfleet.specs import ParamAxis
    from predictionio_tpu.fleet.coordinator import FleetConfig, FleetMember

    folds = 2 if SMALL else 4
    points = 6 if SMALL else 8
    train_cost_s = 0.4 if SMALL else 1.0
    weights = [round(0.05 + 0.08 * i, 3) for i in range(points)]

    def _variant(cost):
        return {
            "id": "bench-grid",
            "engineFactory": "sample_engine.GridEngineFactory",
            "datasource": {"params": {"folds": folds, "queries": 8}},
            "preparator": {"params": {"id": 1}},
            "algorithms": [{
                "name": "grid",
                "params": {"weight": 0.0, "train_cost_s": cost},
            }],
            "serving": {},
        }

    spec = EvalSpec(
        variant=_variant(train_cost_s),
        axes=[ParamAxis("algorithms.0.params.weight", weights)],
        metric={"class": "sample_engine.GridScore"},
        folds=folds,
    )
    out: dict = {}
    tmp = tempfile.mkdtemp(prefix="bench-evalfleet-")
    members = []
    try:
        storage = Storage(StorageConfig(
            sources={
                "SQL": SourceConfig(
                    "SQL", "sqlite", {"PATH": os.path.join(tmp, "pio.db")}
                ),
                "FS": SourceConfig("FS", "localfs", {"PATH": tmp}),
            },
            repositories={
                "METADATA": "SQL", "EVENTDATA": "SQL", "MODELDATA": "FS",
            },
        ))
        engine = sample_engine.GridEngineFactory().apply()
        ctx = RuntimeContext(storage=storage, mesh=None, mode="eval")

        # sequential reference: the single-process MetricEvaluator over
        # the same splits (grid-batched, folds x one train_grid program)
        eps = [engine.params_from_variant_json(p)
               for p in expand_points(spec)]
        t0 = time.perf_counter()
        eval_data = engine.batch_eval(ctx, eps)
        seq_result = MetricEvaluator(sample_engine.GridScore()).evaluate(
            ctx, None, eval_data, WorkflowParams()
        )
        seq_wall = time.perf_counter() - t0
        # one-program evidence: every prediction of every point carries
        # the FULL group size — N points shared one compiled program per
        # fold (a per-point fallback would stamp 1)
        sizes = {
            p.grid_size
            for _ep, data in eval_data
            for _info, qpas in data
            for _q, p, _a in qpas
        }
        out["evalfleet_grid_one_program"] = int(sizes == {len(eps)})

        # grid-group speedup: the compatible group as one train_grid
        # program vs the solo per-point path, on one fold, at a lighter
        # calibrated cost so the A/B stays bench-sized
        cheap = [
            engine.params_from_variant_json(p)
            for p in expand_points(EvalSpec(
                variant=_variant(0.15),
                axes=[ParamAxis("algorithms.0.params.weight", weights)],
                metric={"class": "sample_engine.GridScore"},
                folds=folds,
            ))
        ]
        t0 = time.perf_counter()
        engine.batch_eval(ctx, cheap, fold_indices=[0])
        grid_wall = time.perf_counter() - t0
        t0 = time.perf_counter()
        for ep in cheap:
            engine.eval(ctx, ep, fold_indices=[0])
        solo_wall = time.perf_counter() - t0
        out["evalfleet_grid_group_speedup"] = round(
            solo_wall / max(grid_wall, 1e-9), 2
        )

        # the fleet: 2 workers x 2 slots CAS-claiming per-fold shards
        for i in range(2):
            member = FleetMember(
                storage,
                scheduler_config=SchedulerConfig(
                    poll_interval_s=0.05,
                    heartbeat_interval_s=0.2,
                    stale_after_s=10.0,
                    max_concurrent=2,
                    log_dir=os.path.join(tmp, f"w{i}-logs"),
                    child_env={
                        "PYTHONPATH": os.pathsep.join(
                            [repo_dir, tests_dir]
                        ),
                        "JAX_PLATFORMS": "cpu",
                    },
                ),
                fleet_config=FleetConfig(
                    heartbeat_interval_s=0.2, adaptive_settle=False
                ),
            )
            member.start()
            members.append(member)
        driver = EvalDriver(
            storage, EvalDriverConfig(poll_interval_s=0.1)
        )
        t0 = time.perf_counter()
        run = driver.submit(spec)
        run = driver.wait(run.id, timeout_s=600)
        fleet_wall = time.perf_counter() - t0
        assert run.status == "completed", run.last_error
        assert run.winner_index == seq_result.best_index
        fleet_scores = driver.scores(run)
        for got, ref in zip(fleet_scores, seq_result.engine_params_scores):
            assert abs(got["score"] - ref.score) < 1e-5

        out["evalfleet_fleet_wall_s"] = round(fleet_wall, 3)
        out["evalfleet_sequential_wall_s"] = round(seq_wall, 3)
        out["evalfleet_fleet_vs_sequential"] = round(
            seq_wall / max(fleet_wall, 1e-9), 2
        )

        # records-fold overhead: one full status recompute (durable
        # records + job states folded into the live view)
        times = []
        for _ in range(5):
            t0 = time.perf_counter()
            driver.status(run.id)
            times.append(time.perf_counter() - t0)
        out["evalfleet_records_fold_ms"] = round(
            float(np.percentile(times, 50)) * 1e3, 3
        )
        out["evalfleet_points"] = points
        out["evalfleet_folds"] = folds
        out["evalfleet_shards"] = len(run.shards)
        out["evalfleet_workers"] = len(members)
    finally:
        for member in members:
            member.stop()
        shutil.rmtree(tmp, ignore_errors=True)
    out["host_cpus"] = os.cpu_count()
    out["note"] = (
        f"{points}-point grid x {folds} folds, calibrated "
        f"{train_cost_s}s train program; fleet = 2 workers x 2 slots on "
        "shared sqlite, per-fold shard jobs, durable partial records; "
        "sequential = in-process MetricEvaluator on identical splits; "
        "group speedup = one train_grid program vs per-point training "
        "at 0.15s cost on one fold"
    )
    return out


def main():
    rows, cols, vals = make_data()
    tpu = bench_tpu(rows, cols, vals)
    baseline = bench_numpy_baseline(rows, cols, vals)
    grid = bench_grid_tuning()
    dev_p50_ms, dev_qps = bench_serving_device()
    kernels = bench_serving_kernels()
    batching_ab = bench_batching_ab()
    framework = bench_serving_framework()
    multitenant = bench_multitenant()
    ur = bench_ur_framework()
    ingest = bench_event_ingestion()
    ingest_sharded = bench_sharded_ingestion()
    data_plane = bench_data_plane()
    fleet = bench_fleet()
    dense = tpu.get("dense")
    primary = dense if dense is not None else tpu
    thr = primary["throughput"]
    mean = float(np.mean(thr))
    print(json.dumps({
        "metric": "als_implicit_train_throughput_ml20m"
        if not SMALL else "als_implicit_train_throughput",
        "value": round(mean, 1),
        "unit": "events/sec/chip",
        "device": device_summary(),
        "vs_baseline": round(mean / baseline["events_per_sec"], 3),
        "solver_path": (
            f"dense-{dense['dtype']}" if dense is not None
            else ("pallas" if tpu["pallas"] else "xla")
        ),
        "runs": [round(r, 1) for r in thr],
        "min": round(float(np.min(thr)), 1),
        "std": round(float(np.std(thr)), 1),
        "std_pct": round(100 * float(np.std(thr)) / mean, 2),
        "device_secs": [round(r, 3) for r in primary["runs_sec"]],
        "compile_sec": round(primary["compile_sec"], 1),
        "host_prep_sec": round(primary["host_prep_sec"], 2),
        "transfer_sec": round(primary["transfer_sec"], 2),
        "e2e_train_sec": round(tpu["e2e_sec"], 2),
        "mfu": round(primary["mfu"], 6),
        "hbm_gbps": round(primary["hbm_gbps"], 1),
        "hbm_pct_of_roof": round(100 * primary["hbm_pct_of_roof"], 1),
        "bytes_model_gb": round(primary["bytes_model_gb"], 1),
        **({
            "dense_speedup_vs_windowed": round(
                dense["speedup_vs_windowed"], 2
            ),
            "dense_mxu_util_executed": round(
                100 * dense["mxu_util_executed"], 1
            ),
            "dense_factor_corr_users": round(
                dense["factor_corr_users"], 5
            ),
            "dense_factor_corr_items": round(
                dense["factor_corr_items"], 5
            ),
        } if dense is not None else {}),
        "windowed_events_per_sec": round(
            float(np.mean(tpu["throughput"])), 1  # mean, like the headline
        ),
        "windowed_device_best_sec": round(tpu["device_best_sec"], 3),
        "windowed_edge_pass": "pallas" if tpu["pallas"] else "xla",
        "windowed_hbm_pct_of_roof": round(
            100 * tpu["hbm_pct_of_roof"], 1
        ),
        "pallas_speedup": round(tpu["pallas_speedup"], 3),
        "xla_device_best_sec": round(tpu["xla_path"]["device_best_sec"], 3),
        "xla_events_per_sec": round(
            max(tpu["xla_path"]["throughput"]), 1
        ),
        "xla_hbm_gbps": round(tpu["xla_path"]["hbm_gbps"], 1),
        "xla_hbm_pct_of_roof": round(
            100 * tpu["xla_path"]["hbm_pct_of_roof"], 1
        ),
        "algorithmic_min_gb": round(tpu["algorithmic_min_gb"], 1),
        "cpu_baseline_events_per_sec": round(baseline["events_per_sec"], 1),
        "cpu_baseline_std": round(baseline["std"], 1),
        "cpu_baseline_sample_events": baseline["sample_events"],
        "cpu_baseline_iters": baseline["iters"],
        "als_grid_speedup_4pt": round(grid["speedup"], 2),
        "als_grid_sec": round(grid["grid_sec"], 2),
        "als_grid_seq_sec": round(grid["seq_sec"], 2),
        "als_rank_grid_speedup_2x2": round(grid["rank_grid_speedup"], 2),
        "als_rank_grid_sec": round(grid["rank_grid_sec"], 2),
        "als_rank_grid_seq_sec": round(grid["rank_seq_sec"], 2),
        "serving_device_p50_ms": round(dev_p50_ms, 2),
        "serving_device_qps": round(dev_qps, 1),
        # ISSUE 11: staged serving kernels — fused mode + dtype ladder
        "serving_fused_mode": kernels["f32"]["mode"],
        "serving_fused_p50_ms": round(kernels["f32"]["p50_ms"], 3),
        "serving_fused_qps": round(kernels["f32"]["qps"], 1),
        "serving_int8_p50_ms": round(kernels["int8"]["p50_ms"], 3),
        "serving_int8_qps": round(kernels["int8"]["qps"], 1),
        "serving_int8_score_rel_err": round(kernels["int8_rel_err"], 5),
        "serving_int8_resident_mb": round(
            kernels["int8"]["resident_mb"], 2
        ),
        "serving_f32_resident_mb": round(
            kernels["f32"]["resident_mb"], 2
        ),
        # ISSUE 14: bf16 middle ground + fused similar/CCO + packed
        # masks + the sharded int8 tier
        "serving_bf16_p50_ms": round(kernels["bf16"]["p50_ms"], 3),
        "serving_bf16_qps": round(kernels["bf16"]["qps"], 1),
        "serving_bf16_resident_mb": round(
            kernels["bf16"]["resident_mb"], 2
        ),
        "serving_similar_fused_p50_ms": round(
            kernels["f32"]["similar_p50_ms"], 3
        ),
        "serving_similar_int8_p50_ms": round(
            kernels["int8"]["similar_p50_ms"], 3
        ),
        "serving_cco_p50_ms": round(kernels["cco_p50_ms"], 3),
        "serving_cco_mode": kernels["cco_mode"],
        "serving_mask_packed_bytes_ratio": round(
            kernels["mask_packed_bytes_ratio"], 1
        ),
        **({
            "serving_sharded_int8_resident_mb": round(
                kernels["sharded"]["int8_resident_mb_per_shard"], 2
            ),
            "serving_sharded_int8_over_f32": round(
                kernels["sharded"]["int8_over_f32_resident"], 3
            ),
            "serving_sharded_int8_p50_ms": round(
                kernels["sharded"]["int8_p50_ms"], 3
            ),
            "serving_sharded_publish_dirty16_ms": round(
                kernels["sharded"]["publish_dirty16_ms"], 3
            ),
            "serving_sharded_shards": kernels["sharded"]["shards"],
        } if kernels.get("sharded") else {}),
        # ISSUE 11: continuous vs windowed batching under load
        "serving_batching_continuous_qps": round(
            batching_ab["continuous"]["qps"], 1
        ),
        "serving_batching_continuous_p99_ms": round(
            batching_ab["continuous"]["p99_ms"], 1
        ),
        "serving_batching_windowed_qps": round(
            batching_ab["windowed"]["qps"], 1
        ),
        "serving_batching_windowed_p99_ms": round(
            batching_ab["windowed"]["p99_ms"], 1
        ),
        "serving_batching_p99_ratio": round(
            batching_ab["p99_ratio"], 3
        ) if batching_ab["p99_ratio"] else None,
        "serving_framework_qps": round(framework["qps"], 1),
        "serving_framework_p50_ms": round(framework["p50_ms"], 1),
        "serving_framework_p99_ms": round(framework["p99_ms"], 1),
        # ISSUE 3: framework-derived (devprof registry) vs hand-derived
        # serving MFU — the acceptance cross-check (agree within 2×)
        **({
            "serving_mfu_framework": round(
                framework["devprof"]["mfu_framework"], 8
            ),
            "serving_mfu_hand": round(
                framework["devprof"]["mfu_hand"], 8
            ),
            "serving_mfu_agreement": round(
                framework["devprof"]["agreement"], 3
            ),
            "serving_padding_mean_ratio": round(
                framework["devprof"]["padding_mean_ratio"], 4
            ),
            "serving_padding_wasted_gflops": round(
                framework["devprof"]["padding_wasted_gflops"], 3
            ),
        } if framework.get("devprof") else {}),
        **({
            "train_devprof": tpu["devprof_train"],
        } if tpu.get("devprof_train") else {}),
        "serving_metrics_registry": framework["obs"],
        "serving_slowest_trace": framework["slowest_trace"],
        "serving_clients": framework["clients"],
        "serving_client_sweep": [
            {"clients": r["clients"], "qps": round(r["qps"], 1),
             "p50_ms": round(r["p50_ms"], 1)}
            for r in framework["sweep"]
        ],
        # ISSUE 6: multi-tenant isolation (1 hog + 3 well-behaved on one
        # server) and model-cache economics (6 live models, 3 slots)
        "mt_solo_p99_ms": multitenant["isolation"]["solo_p99_ms"],
        "mt_nohog_p99_ms": multitenant["isolation"]["nohog_p99_ms"],
        "mt_contended_p99_ms": multitenant["isolation"]["contended_p99_ms"],
        "mt_p99_ratio": multitenant["isolation"]["p99_ratio"],
        "mt_hog_impact_ratio": multitenant["isolation"]["hog_impact_ratio"],
        "mt_goodput_qps": multitenant["isolation"]["goodput_qps"],
        "mt_goodput_ratio": multitenant["isolation"]["goodput_ratio"],
        "mt_in_quota_dropped": multitenant["isolation"]["in_quota_dropped"],
        "mt_hog_served": multitenant["isolation"]["hog_served"],
        "mt_hog_rejected_429": multitenant["isolation"]["hog_rejected_429"],
        "mt_hog_goodput_qps": multitenant["isolation"]["hog_goodput_qps"],
        "mt_cache_live_models": multitenant["cache"]["live_models"],
        "mt_cache_capacity": multitenant["cache"]["capacity"],
        "mt_cache_hit_rate": multitenant["cache"]["hit_rate"],
        "mt_cache_reloads": multitenant["cache"]["reloads"],
        "mt_cache_evictions": multitenant["cache"]["evictions"],
        # ISSUE 9: online learning — ingest→serving-visibility latency
        # for cold-start users (bar: < 2 consumer ticks) and fold-in
        # overhead on serving p99 (bar: < 1.05× vs detached)
        "online_tick_s": framework["online_tick_s"],
        "online_fold_latency_p50_ms": framework["online_fold_latency_p50_ms"],
        "online_fold_latency_max_ms": framework["online_fold_latency_max_ms"],
        "online_fold_latency_ticks": framework["online_fold_latency_ticks"],
        "online_cold_users_visible": framework["online_cold_users_visible"],
        "online_events_folded": framework["online_events_folded"],
        "online_off_p99_ms": framework["online_off_p99_ms"],
        "online_on_p99_ms": framework["online_on_p99_ms"],
        "online_overhead_p99_ratio": framework["online_overhead_p99_ratio"],
        "online_folding_p99_ms": framework["online_folding_p99_ms"],
        "online_folding_p99_ratio": framework["online_folding_p99_ratio"],
        "ur_framework_qps": round(ur["qps"], 1),
        "ur_framework_p50_ms": round(ur["p50_ms"], 1),
        "ur_framework_p99_ms": round(ur["p99_ms"], 1),
        "ur_clients": ur["clients"],
        "ur_client_sweep": [
            {"clients": r["clients"], "qps": round(r["qps"], 1),
             "p50_ms": round(r["p50_ms"], 1)}
            for r in ur["sweep"]
        ],
        "ur_catalog_items": ur["catalog"],
        "ingest_events_per_sec": round(ingest["events_per_sec"], 1),
        "ingest_backend": ingest["backend"],
        "ingest_writers": ingest["writers"],
        "ingest_sharded_host_cpus": ingest_sharded["host_cpus"],
        "ingest_sharded_events_per_sec": [
            {"shards": r["shards"], "front_ends": r["front_ends"],
             "events_per_sec": round(r["events_per_sec"], 1)}
            for r in ingest_sharded["per_shards"]
        ],
        # ISSUE 13: columnar data plane — store-level ingest, the loader
        # A/B (host prep + transfer, tail-only retrain restage), and the
        # consumer tail-read latency
        "ingest_segment_eps": round(data_plane["ingest_segment_eps"], 1),
        "ingest_sqlite_store_eps": round(
            data_plane["ingest_sqlite_store_eps"], 1
        ),
        "ingest_segment_vs_sqlite": round(
            data_plane["ingest_segment_vs_sqlite"], 2
        ),
        "ingest_sharded_segment_eps": round(
            data_plane["ingest_sharded_segment_eps"], 1
        ),
        "ingest_sharded_segment_vs_single": round(
            data_plane["ingest_sharded_segment_vs_single"], 3
        ),
        "ingest_sharded_segment_vs_single_b50": round(
            data_plane["ingest_sharded_segment_vs_single_b50"], 3
        ),
        "loader_rows": data_plane["loader_rows"],
        "loader_row_host_prep_s": round(
            data_plane["loader_row_host_prep_s"], 4
        ),
        "loader_host_prep_s": round(data_plane["loader_host_prep_s"], 4),
        "loader_host_prep_speedup": round(
            data_plane["loader_host_prep_speedup"], 2
        ),
        "loader_row_transfer_s": round(
            data_plane["loader_row_transfer_s"], 4
        ),
        "loader_transfer_s": round(data_plane["loader_transfer_s"], 4),
        "loader_retrain_restage_s": round(
            data_plane["loader_retrain_restage_s"], 4
        ),
        "find_since_tail_p50_ms": round(
            data_plane["find_since_tail_p50_ms"], 3
        ),
        "find_since_tail_sqlite_p50_ms": round(
            data_plane["find_since_tail_sqlite_p50_ms"], 3
        ),
        # ISSUE 10: fleet — dense-train scaling over the (dp, mp) mesh
        # and the oversized-catalog sharded-serving proof
        "fleet_train_scaling": fleet["train_scaling"],
        "fleet_serve_shards": fleet["serve_shards"],
        "workload": f"{N_EVENTS} events, {N_USERS}x{N_ITEMS}, rank {RANK}, "
                    f"{ITERATIONS} iters",
    }))


if __name__ == "__main__":
    import sys as _sys

    from predictionio_tpu.utils.jaxenv import ensure_compile_cache

    ensure_compile_cache()
    if "--data-plane" in _sys.argv:
        # focused ISSUE-13 emission: the data-plane scenario alone, so a
        # bench round on the storage layer doesn't pay for the full
        # train/serve gauntlet
        print(json.dumps(bench_data_plane()))
    elif "--gateway" in _sys.argv:
        # focused ISSUE-15 emission (BENCH_r09): the replicated serving
        # tier alone — stub replicas, no jax, no training
        print(json.dumps(bench_gateway()))
    elif "--fleetobs" in _sys.argv:
        # focused ISSUE-16 emission (BENCH_r10): the observability
        # plane — recording-rule SLO eval + the traced-gateway tax
        print(json.dumps(bench_fleetobs()))
    elif "--push" in _sys.argv:
        # focused ISSUE-17 emission (BENCH_r11): push telemetry —
        # shipper attach tax on serving p99, spool→queryable latency,
        # and series-algebra eval cost
        print(json.dumps(bench_push_telemetry()))
    elif "--durable-tsdb" in _sys.argv:
        # focused ISSUE-18 emission (BENCH_r12): the durable TSDB tier
        # — WAL throughput, cold replay, compaction, and the 3-day
        # downsampled query
        print(json.dumps(bench_durable_tsdb()))
    elif "--replication" in _sys.argv:
        # focused ISSUE-19 emission (BENCH_r13): the replicated event
        # store — acked ingest under min_acks=1, cold-follower
        # catch-up throughput, and promotion-to-first-serve
        print(json.dumps(bench_replication()))
    elif "--eval" in _sys.argv:
        # focused ISSUE-20 emission (BENCH_r14): fleet evaluation —
        # fan-out vs sequential MetricEvaluator, grid-group one-program
        # speedup, and the records-fold status overhead
        print(json.dumps(bench_eval_fleet()))
    else:
        main()
