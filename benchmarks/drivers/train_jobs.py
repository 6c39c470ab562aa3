"""Driver `train_jobs`: whole `run_train` jobs, back to back.

Set-up makes the corpus from --seed, hands it to the benchmark's in-memory
data source, and runs one warm-up job (every program compiled or read back
from the persistent cache, the host's pages touched). The window runs jobs
through the program's normal workflow — data source, preparator,
`ALSAlgorithm.train`, persist, COMPLETED instance — and starts no new job
once --seconds have passed; a job that is running then runs to its end, so
the window closes with its last job. `train_job_s` is the window's wall time
over the jobs finished in it.

`correct`: every job's persisted model is read back from the model store the
way a deploy would, and its factors are held against the plain reference's
(`reference/als_implicit.py`), which trains once on the same corpus after
the window has closed and the program's matrix is freed.
"""

from __future__ import annotations

import gc
import json
import os
import time

import numpy as np

from benchmarks import corpus, engine_factories
from benchmarks.harness import Check, Context

def rehearsal_env() -> dict:
    # under a million pairs the program keeps its exact windowed path unless
    # told otherwise; the rehearsal has to walk the dense one the cell times
    return {"PIO_DENSE_ALS": "1"}


def prepare(ctx: Context) -> dict:
    """Corpus, data source and a fresh store: everything but the warm-up."""
    from predictionio_tpu.data.storage.registry import Storage, StorageConfig
    from predictionio_tpu.data.store.bimap import BiMap
    from predictionio_tpu.engines.recommendation.engine import TrainingData

    cfg = ctx.config
    t0 = time.monotonic()
    rows, cols, vals = corpus.make_corpus(
        cfg["n_users"], cfg["n_items"], cfg["n_interactions"], ctx.seed
    )
    ctx.log(f"corpus of {len(rows)} pairs made in {time.monotonic() - t0:.1f}s")
    engine_factories.CORPORA[cfg["name"]] = TrainingData(
        rows=rows, cols=cols, vals=vals,
        n_users=cfg["n_users"], n_items=cfg["n_items"],
        user_vocab=BiMap({f"u{i}": i for i in range(cfg["n_users"])}),
        item_vocab=BiMap({f"i{i}": i for i in range(cfg["n_items"])}),
    )
    storage = Storage(
        StorageConfig.default_dev(os.path.join(ctx.state_dir, "pio"))
    )
    return {
        "storage": storage, "variant": engine_factories.variant_of(cfg),
        "corpus": (rows, cols, vals),
    }


def setup(ctx: Context) -> dict:
    session = prepare(ctx)
    t0 = time.monotonic()
    run_job(session)
    ctx.log(f"warm-up job took {time.monotonic() - t0:.1f}s")
    return session


def run_job(session: dict) -> dict:
    from predictionio_tpu.workflow.core import run_train

    t0 = time.monotonic()
    instance = run_train(session["storage"], session["variant"])
    seconds = time.monotonic() - t0
    if instance.status != "COMPLETED":
        raise RuntimeError(f"train job ended {instance.status}")
    env = instance.env or {}
    return {
        "instance_id": instance.id,
        "seconds": seconds,
        "stage_timings": json.loads(env.get("stage_timings", "{}")),
        "device_profile": json.loads(env.get("device_profile", "{}")),
    }


def window(session: dict, ctx: Context) -> dict:
    jobs, failed = [], 0
    t0 = time.monotonic()
    while time.monotonic() - t0 < ctx.seconds:
        try:
            jobs.append(run_job(session))
        except Exception as e:  # a failed job is counted, the window goes on
            ctx.log(f"job failed: {type(e).__name__}: {e}")
            failed += 1
            if failed >= 3:
                break
    wall = time.monotonic() - t0
    out = {
        "attempted": len(jobs) + failed,
        "failed": failed,
        "jobs": jobs,
        "window_s": wall,
        "end_to_end": {},
        "notes": {
            "jobs": len(jobs),
            "job_seconds": [round(j["seconds"], 3) for j in jobs],
            "stage_timings": [j["stage_timings"] for j in jobs],
        },
    }
    if jobs:
        out["end_to_end"]["train_job_s"] = wall / len(jobs)
    return out


def persisted_factors(storage, instance_id: str):
    from predictionio_tpu.controller.persistent import deserialize_models

    blob = storage.get_model_data_models().get(instance_id)
    f = deserialize_models(blob.models)[0].factors
    return (
        np.asarray(f.user_factors), np.asarray(f.item_factors),
        f.user_vocab.to_dict(), f.item_vocab.to_dict(),
    )


def reference_factors(ctx: Context, session: dict, operand_bits=None,
                      keep=slice(None)):
    from benchmarks.reference import als_implicit

    cfg, algo = ctx.config, ctx.config["algorithm"]
    rows, cols, vals = (a[keep] for a in session["corpus"])
    return als_implicit.train(
        rows, cols, vals, cfg["n_users"], cfg["n_items"],
        rank=algo["rank"], iterations=algo["num_iterations"],
        lambda_=algo["lambda_"], alpha=algo["alpha"],
        cg_iterations=algo["cg_iterations"], seed=algo["seed"],
        operand_bits=operand_bits,
    )


def compare(ctx: Context, persisted: list, ref_u, ref_i) -> list[Check]:
    """`persisted`: (user_factors, item_factors, user_vocab, item_vocab) of
    every job. Each number is the worst over the jobs."""
    from benchmarks.reference import compare as cmp

    limits = ctx.plan.workload["limits"]
    cfg = ctx.config
    table = row = 0.0
    vocab = 0
    for uf, itf, uv, iv in persisted:
        if uf.shape != ref_u.shape or itf.shape != ref_i.shape:
            table = row = float("inf")
            continue
        table = max(table, cmp.table_gap(uf, ref_u), cmp.table_gap(itf, ref_i))
        row = max(row, cmp.worst_row_gap(uf, ref_u),
                  cmp.worst_row_gap(itf, ref_i))
        vocab += cmp.vocab_mismatches(uv, "u", cfg["n_users"])
        vocab += cmp.vocab_mismatches(iv, "i", cfg["n_items"])
    return [
        Check("factors_table_gap", table, limits["factors_table_gap"]),
        Check("factors_worst_row_gap", row, limits["factors_worst_row_gap"]),
        Check("vocab_mismatches", float(vocab), 0.0),
        Check("jobs_without_model", float(len(persisted) == 0), 0.0),
    ]


def check(session: dict, ctx: Context, win: dict) -> list[Check]:
    persisted = [
        persisted_factors(session["storage"], j["instance_id"])
        for j in win["jobs"]
    ]
    engine_factories.CORPORA.clear()
    gc.collect()
    t0 = time.monotonic()
    ref_u, ref_i = reference_factors(ctx, session)
    ctx.log(f"reference trained in {time.monotonic() - t0:.1f}s")
    return compare(ctx, persisted, ref_u, ref_i)


def prove(ctx: Context, controls: bool) -> dict:
    """One job of the program against the reference, for setting limits; with
    `controls` also the control (the reference in the program's place with
    the edge pass's operands rounded through fp8, the nearest precision
    below the bf16 the configuration states) and the faults the cell can
    have, each planted in the reference put in the program's place: the
    state returned unchanged (the start), half of the pairs left out, one
    row of the answer altered."""
    from benchmarks.reference import als_implicit

    session = prepare(ctx)
    cfg, algo = ctx.config, ctx.config["algorithm"]
    job = run_job(session)
    persisted = [persisted_factors(session["storage"], job["instance_id"])]
    uv, iv = persisted[0][2], persisted[0][3]
    engine_factories.CORPORA.clear()
    gc.collect()
    ref_u, ref_i = reference_factors(ctx, session)

    def numbers(uf, itf):
        return {c.name: c.value
                for c in compare(ctx, [(uf, itf, uv, iv)], ref_u, ref_i)}

    out = {"job_s": job["seconds"], "program": numbers(*persisted[0][:2])}
    if controls:
        out["control_fp8"] = numbers(*reference_factors(
            ctx, session, operand_bits=als_implicit.FP8_E4M3))
        x0, y0 = als_implicit.initial_factors(
            algo["seed"], cfg["n_users"], cfg["n_items"], algo["rank"])
        out["fault_state_unchanged"] = numbers(np.asarray(x0), np.asarray(y0))
        out["fault_half_left_out"] = numbers(*reference_factors(
            ctx, session, keep=slice(0, None, 2)))
        altered = ref_i.copy()
        # a row of upper-quartile norm: an all-but-zero row altered is no
        # fault that anyone would see
        row = np.argsort(np.linalg.norm(ref_i, axis=1))[3 * len(ref_i) // 4]
        altered[row] *= 1.5
        out["fault_row_altered"] = numbers(ref_u, altered)
    teardown(session)
    return out


def teardown(session: dict) -> None:
    engine_factories.CORPORA.clear()
