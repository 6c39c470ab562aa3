"""Driver `ur_train_jobs`: whole Universal Recommender `run_train` jobs over
Taobao UserBehavior's 100 M events, back to back.

Set-up checks that the program has the sparse train path (a program
without it asks numpy for a (users x items) matrix, 16 TB here), makes the
events from --seed (`ur_train_data.make_events`), hands them to the
benchmark's in-memory data source, and runs one warm-up job. The window
runs jobs through the program's normal workflow — data source, preparator,
`URAlgorithm.train`, persist, COMPLETED instance — and starts no new job
once --seconds have passed; a job that is running then runs to its end.
`train_job_s` is the mean wall time of a `run_train` call.

Between two jobs, outside their timing, the job's persisted model is read
back from the model store as a deploy would read it, the rows the
comparison needs are kept, and the blob is deleted: a model is 6.7 GB, so
the store holds one at a time.

`correct`: every job's model against the plain float64 reference
(`reference/ur_cco.py`), which redoes the downsampling from the
configuration's text after the window and counts ~2,000 sampled primary
items' co-occurrences an indicator from the raw event lists.
"""

from __future__ import annotations

import gc
import json
import time

import numpy as np

from benchmarks import ur_train_data
from benchmarks.harness import BenchmarkError, Check, Context


def rehearsal_env() -> dict:
    return {}


def require_sparse_train() -> None:
    """Fail at once where the program cannot train this deployment: a tree
    without the sparse path would build a dense (users x items) float32
    matrix an indicator."""
    from predictionio_tpu.models import cco

    if not hasattr(cco, "join_indicators"):
        raise BenchmarkError(
            "this program has no sparse cross-occurrence train path "
            "(models/cco.py join_indicators): its URAlgorithm.train builds a "
            "dense (users x items) matrix an indicator")


def prepare(ctx: Context) -> dict:
    """Events, vocabularies, the data source and a fresh store."""
    from predictionio_tpu.data.storage.registry import (
        SourceConfig,
        Storage,
        StorageConfig,
    )
    from predictionio_tpu.data.store.bimap import BiMap
    from benchmarks.reference import ur_cco

    require_sparse_train()
    cfg = ctx.config
    t0 = time.monotonic()
    events = ur_train_data.make_events(cfg, ctx.seed)
    ctx.log(f"{sum(r.size for r, _c in events.values())} events made in "
            f"{time.monotonic() - t0:.1f}s")
    t0 = time.monotonic()
    items = BiMap({f"i{i}": i for i in range(cfg["n_items"])})
    users = BiMap({f"u{u}": u for u in range(cfg["n_users"])})
    ctx.log(f"vocabularies made in {time.monotonic() - t0:.1f}s")
    ur_train_data.CORPORA[cfg["name"]] = ur_train_data.training_data(
        cfg, events, items, users)
    # the model store in memory: a job's blob is 6.7 GB and is deleted once
    # it has been read back
    storage = Storage(StorageConfig(
        sources={"MEM": SourceConfig("MEM", "memory", {})},
        repositories={"METADATA": "MEM", "EVENTDATA": "MEM",
                      "MODELDATA": "MEM"},
    ))
    return {
        "storage": storage,
        "variant": ur_train_data.variant_of(cfg),
        "events": events,
        "sampled": ur_cco.sample_items(events, cfg, ctx.seed),
    }


def setup(ctx: Context) -> dict:
    session = prepare(ctx)
    t0 = time.monotonic()
    session["warmup"] = run_job(session, ctx)
    ctx.log(f"warm-up job took {time.monotonic() - t0:.1f}s")
    if ctx.trace:  # the join roofline's count, before the traced window
        t0 = time.monotonic()
        session["pairs"] = pair_counts(session, ctx)
        ctx.log(f"join work counted in {time.monotonic() - t0:.1f}s")
    return session


def run_job(session: dict, ctx: Context) -> dict:
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
        "model": read_back(session, ctx, instance.id),
    }


def read_back(session: dict, ctx: Context, instance_id: str) -> dict:
    """What the comparison reads of a persisted model, read back as a
    deploy reads it; the blob is deleted from the store after."""
    from predictionio_tpu.controller.persistent import deserialize_models

    store = session["storage"].get_model_data_models()
    model = deserialize_models(store.get(instance_id).models)[0]
    store.delete(instance_id)
    cfg = ctx.config
    rows = session["sampled"]
    out = {"names": [m.name for m in model.indicator_models],
           "items": len(model.item_vocab), "served": {}, "diagonal": 0,
           "shapes_ok": True}
    shape = (int(cfg["n_items"]), int(cfg["algorithm"]["max_correlators_per_item"]))
    for m in model.indicator_models:
        idx, sc = m.correlator_idx, m.correlator_scores
        if idx.shape != shape or sc.shape != shape:
            out["shapes_ok"] = False
            continue
        out["served"][m.name] = (rows, idx[rows].copy(), sc[rows].copy())
        if m.name == model.primary_indicator:
            out["diagonal"] = int(np.count_nonzero(
                idx == np.arange(shape[0], dtype=idx.dtype)[:, None]))
    del model
    gc.collect()
    return out


def window(session: dict, ctx: Context) -> dict:
    jobs, failed = [], 0
    t0 = time.monotonic()
    while time.monotonic() - t0 < ctx.seconds:
        try:
            jobs.append(run_job(session, ctx))
        except Exception as e:  # a failed job is counted, the window goes on
            ctx.log(f"job failed: {type(e).__name__}: {e}")
            failed += 1
            if failed >= 3:
                break
    out = {
        "attempted": len(jobs) + failed,
        "failed": failed,
        "jobs": jobs,
        "window_s": time.monotonic() - t0,
        "end_to_end": {},
        "pairs": session.get("pairs"),
        "notes": {
            "jobs": len(jobs),
            "job_seconds": [round(j["seconds"], 3) for j in jobs],
            "stage_timings": [j["stage_timings"] for j in jobs],
        },
    }
    if jobs:
        out["end_to_end"]["train_job_s"] = (
            sum(j["seconds"] for j in jobs) / len(jobs))
    return out


def pair_counts(session: dict, ctx: Context) -> dict:
    """What the join has to do, counted by the benchmark from its own
    downsampled events (the reference's), never from the program's
    counters: per indicator the kept events, the primary x indicator pairs
    (the diagonal out) and the distinct pairs among them."""
    from benchmarks.reference import ur_cco

    ref = session.get("reference") or ur_cco.Reference(
        ctx.config, session["events"])
    session["reference"] = ref
    return ur_cco.join_work(ref)


def reference_rows(ref, items) -> dict:
    return {name: {int(i): ref.row(name, int(i)) for i in items}
            for name in ref.names}


def compare(ctx: Context, models: list, ref_rows: dict) -> list[Check]:
    """Each number the worst over the jobs' models."""
    from benchmarks.reference import ur_cco

    limits = ctx.plan.workload["limits"]
    cfg = ctx.config
    worst = {k: 0.0 for k in limits}
    malformed = 0.0
    for m in models:
        if (not m["shapes_ok"] or m["names"] != list(cfg["indicators"])
                or m["items"] != cfg["n_items"]):
            malformed += 1
            continue
        got = ur_cco.compare_rows(ref_rows, m["served"],
                                  int(cfg["algorithm"]["max_correlators_per_item"]))
        got["cco_diagonal"] = float(m["diagonal"])
        malformed += got.pop("cco_malformed_rows")
        for k in worst:
            worst[k] = max(worst[k], got[k])
    return [Check(k, v, limits[k]) for k, v in worst.items()] + [
        Check("cco_malformed_models_and_rows", malformed, 0.0),
        Check("jobs_without_model", float(len(models) == 0), 0.0),
    ]


def check(session: dict, ctx: Context, win: dict) -> list[Check]:
    from benchmarks.reference import ur_cco

    models = [j["model"] for j in win["jobs"]]
    ur_train_data.CORPORA.clear()
    gc.collect()
    t0 = time.monotonic()
    ref = session.get("reference") or ur_cco.Reference(
        ctx.config, session["events"])
    rows = reference_rows(ref, session["sampled"])
    ctx.log(f"reference made in {time.monotonic() - t0:.1f}s")
    return compare(ctx, models, rows)


def prove(ctx: Context, controls: bool) -> dict:
    """One job of the program against the reference, for setting limits;
    with `controls` also the controls — the reference's own counts scored
    by the LLR in bfloat16 (the precision below the float32 the
    configuration states) and by the entropy form the program had before
    PR 39, in float32 — and the faults the cell can have, each planted in
    the reference put in the program's place: a row's last correlator
    dropped, the downsampling skipped."""
    from benchmarks.reference import ur_cco

    session = prepare(ctx)
    job = run_job(session, ctx)
    ur_train_data.CORPORA.clear()
    ref = ur_cco.Reference(ctx.config, session["events"])
    items = session["sampled"]
    rows = reference_rows(ref, items)

    def numbers(model) -> dict:
        return {c.name: c.value for c in compare(ctx, [model], rows)}

    def as_model(tables) -> dict:
        return dict(job["model"], served=tables, diagonal=0)

    out = {"job_s": job["seconds"], "program": numbers(job["model"])}
    if controls:
        out["control_bf16"] = numbers(as_model(
            ur_cco.reference_tables(ref, items, llr_bf16)))
        out["control_entropy_f32"] = numbers(as_model(
            ur_cco.reference_tables(ref, items, llr_entropy_f32)))
        truncated = ur_cco.reference_tables(ref, items)
        for _items, idx, sc in truncated.values():
            last = (idx >= 0).sum(axis=1) - 1
            live = last >= 0
            idx[live, last[live]] = -1
            sc[live, last[live]] = 0.0
        out["fault_last_correlator_dropped"] = numbers(as_model(truncated))
        cfg = dict(ctx.config, algorithm=dict(
            ctx.config["algorithm"], max_events_per_event_type=2**31 - 1))
        unsampled = ur_cco.Reference(cfg, session["events"])
        out["fault_no_downsampling"] = numbers(as_model(
            ur_cco.reference_tables(unsampled, items)))
    teardown(session)
    return out


def llr_bf16(k11, r, c, n):
    """Control: the program's form of the LLR (`models/cco.py` `llr`: the
    four cells' E·φ(δ/E), a series near δ = 0; the backend's log1p in
    place of the program's own log), every operation in bfloat16."""
    import jax.numpy as jnp

    bf = jnp.bfloat16
    k11, r, c, n = (jnp.asarray(x, bf) for x in (k11, r, c, n))

    def cell(e, u):
        small = jnp.abs(u) < 0.1
        us = jnp.where(small, u, 0)
        series = us * us * (0.5 + us * (-1 / 6 + us * (1 / 12 + us * (
            -1 / 20 + us * (1 / 30 + us * (-1 / 42))))))
        ud = jnp.where(small, 1, jnp.maximum(u, -1))
        direct = jnp.where(ud > -1, (1 + ud) * jnp.log1p(ud), 0) - ud
        return e * jnp.where(small, series, direct)

    pr, pc = r / n, c / n
    e11 = r * pc
    e = k11 - e11
    e12, e21, e22 = r * (1 - pc), (1 - pr) * c, n * (1 - pr) * (1 - pc)
    out = 2 * (cell(e11, k11 / e11 - 1) + cell(e12, -e / e12)
               + cell(e21, -e / e21) + cell(e22, e / e22))
    return np.asarray(jnp.maximum(out, 0).astype(jnp.float32))


def llr_entropy_f32(k11, r, c, n):
    """Control: the entropy form the program computed before PR 39,
    2 (sum x log x of the cells - of the rows - of the columns + n log n),
    in float32."""
    f = np.float32
    k11, r, c, n = (np.asarray(x, f) for x in (k11, r, c, n))
    k12, k21 = r - k11, c - k11
    k22 = n - k11 - k12 - k21

    def xlx(x):
        return np.where(x > 0, x * np.log(np.maximum(x, f(1e-30))), f(0))

    out = f(2) * (xlx(k11) + xlx(k12) + xlx(k21) + xlx(k22)
                  - xlx(k11 + k12) - xlx(k21 + k22)
                  - xlx(k11 + k21) - xlx(k12 + k22) + xlx(n))
    return np.maximum(out, f(0))


def teardown(session: dict) -> None:
    ur_train_data.CORPORA.clear()
