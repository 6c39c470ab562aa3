"""What the query drivers share: the factor tables from the seed, a live
`QueryServer` over an `EngineRuntime` built by `build_runtime`'s own steps,
the generator processes, and the comparison of a sample of replies with the
plain reference.

The runtime is built from an in-memory `ALSModel`, not from a model blob:
pickling 5.8 GB of factors and two 5.7 M-entry vocabularies and reading them
back would write ~6 GB to disk in every run and add tens of seconds of
set-up that serves no query. Everything after the blob is `build_runtime`'s:
the engine from its factory, params from the variant, `make_algorithms`,
`make_serving`, `set_serving_context`, the algorithm's own `warmup`, then
`QueryServer.start()` on a local port and `POST /queries.json` from outside.
"""

from __future__ import annotations

import datetime as dt
import gc
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np

from benchmarks import engine_factories
from benchmarks.harness import HERE, Check, Context

#: the id vocabularies (`u<row>`, `i<row>`) depend on the sizes alone; a
#: process that proves many seeds (prove.py) builds them once
_VOCABS: dict[tuple[str, int], object] = {}


def make_tables(n_users: int, n_items: int, rank: int, seed: int,
                threads: int = 8):
    """(user_factors, item_factors): float32 normal / sqrt(rank), filled in
    row chunks by `threads` generators spawned from the seed — the chunking
    is fixed, so the tables depend on the seed alone."""
    out = []
    for t, n in enumerate((n_users, n_items)):
        table = np.empty((n, rank), np.float32)
        chunks = 64
        bounds = np.linspace(0, n, chunks + 1).astype(np.int64)
        seeds = np.random.SeedSequence([seed % (2**32), 31, t]).spawn(chunks)

        def fill(ids):
            for c in ids:
                lo, hi = bounds[c], bounds[c + 1]
                np.random.default_rng(seeds[c]).standard_normal(
                    (hi - lo, rank), dtype=np.float32, out=table[lo:hi])
                table[lo:hi] *= np.float32(1.0 / np.sqrt(rank))

        workers = [threading.Thread(target=fill, args=(range(w, chunks, threads),))
                   for w in range(threads)]
        for w in workers:
            w.start()
        for w in workers:
            w.join()
        out.append(table)
    return out[0], out[1]


def build_session(ctx: Context, serve_dtype: str | None = None) -> dict:
    from predictionio_tpu.controller.engine import resolve_engine
    from predictionio_tpu.core.base import RuntimeContext
    from predictionio_tpu.data.storage.base import EngineInstance
    from predictionio_tpu.data.storage.registry import Storage, StorageConfig
    from predictionio_tpu.data.store.bimap import BiMap
    from predictionio_tpu.engines.recommendation.engine import ALSModel
    from predictionio_tpu.models import als
    from predictionio_tpu.obs.jaxmon import ensure_compile_listener
    from predictionio_tpu.controller.params import load_symbol
    from predictionio_tpu.workflow.server import (
        EngineRuntime,
        QueryServer,
        QueryServerConfig,
    )

    cfg, algo = ctx.config, dict(ctx.config["algorithm"])
    if serve_dtype is not None:
        algo["serve_dtype"] = serve_dtype
    t0 = time.monotonic()
    uf, itf = make_tables(cfg["n_users"], cfg["n_items"], algo["rank"], ctx.seed)
    ctx.log(f"factor tables made in {time.monotonic() - t0:.1f}s")
    t0 = time.monotonic()
    for prefix, n in (("u", cfg["n_users"]), ("i", cfg["n_items"])):
        if (prefix, n) not in _VOCABS:
            _VOCABS[(prefix, n)] = BiMap({f"{prefix}{i}": i for i in range(n)})
    factors = als.ALSFactors(
        user_factors=uf, item_factors=itf,
        user_vocab=_VOCABS[("u", cfg["n_users"])],
        item_vocab=_VOCABS[("i", cfg["n_items"])],
        params=als.ALSParams(rank=algo["rank"]),
    )
    ctx.log(f"vocabularies made in {time.monotonic() - t0:.1f}s")
    model = ALSModel(factors, serve_dtype=algo.get("serve_dtype", "f32"))

    storage = Storage(StorageConfig.default_dev(os.path.join(ctx.state_dir, "pio")))
    variant = engine_factories.variant_of(cfg, algo)
    ensure_compile_listener()
    engine = resolve_engine(load_symbol(variant["engineFactory"]))
    engine_params = engine.params_from_variant_json(variant)
    now = dt.datetime.now(dt.timezone.utc)
    instance = EngineInstance(
        id="", status="COMPLETED", start_time=now, end_time=now,
        engine_id=variant["id"], engine_version="0",
        engine_variant=variant["id"], engine_factory=variant["engineFactory"],
    )
    instance.id = storage.get_meta_data_engine_instances().insert(instance)
    algorithms = engine.make_algorithms(engine_params)
    serving = engine.make_serving(engine_params)
    serving_ctx = RuntimeContext(storage=storage, mode="serve")
    t0 = time.monotonic()
    for a in algorithms:
        a.set_serving_context(serving_ctx)
        a.warmup(model)  # stages the slabs and runs every bucket's programs
    ctx.log(f"staged and warmed up in {time.monotonic() - t0:.1f}s")
    runtime = EngineRuntime(
        instance=instance, engine=engine, engine_params=engine_params,
        algorithms=algorithms, models=[model], serving=serving,
        query_class=algorithms[0].query_class(),
        query_serializer=algorithms[0].query_serializer(),
    )
    server = QueryServer(storage, runtime, QueryServerConfig(ip="127.0.0.1", port=0))
    port = server.start()
    return {
        "server": server, "port": port, "runtime": runtime, "model": model,
        "tables": (uf, itf), "ctx": ctx, "procs": [],
    }


def rehearsal_env() -> dict:
    return {"PIO_PALLAS_RECOMMEND": "interpret"}


def setup(ctx: Context) -> dict:
    session = build_session(ctx)
    warm_http(session, ctx.traffic, ctx.seed)
    return session


def compiles_so_far() -> float:
    from predictionio_tpu.obs.jaxmon import compile_snapshot

    return float(compile_snapshot()[0])


def histogram_state(server, name: str) -> tuple[float, float]:
    """(count, sum) of one of the server registry's histograms."""
    for fam in server.metrics.families():
        if fam.name == name:
            return float(fam.count), float(fam.sum)
    return 0.0, 0.0


def run_generators(session: dict, traffic: dict, seconds: float, seed: int,
                   tag: str) -> list[dict]:
    """Start the generator processes, wait for the window and for every
    reply that is still due (a reply that comes late is late, not wrong),
    and return what each wrote."""
    ctx: Context = session["ctx"]
    cfg = ctx.config
    procs = int(traffic.get("generator_procs", 2))
    traffic_file = os.path.join(ctx.state_dir, f"traffic-{tag}.json")
    with open(traffic_file, "w") as f:
        json.dump(traffic, f)
    # the generators build their Zipf table first; the window starts for all
    # of them at the same instant, after the slowest is ready
    start_at = time.time() + float(traffic.get("generator_start_s", 4.0))
    children, outs = [], []
    for i in range(procs):
        out = os.path.join(ctx.state_dir, f"gen-{tag}-{i}.json")
        outs.append(out)
        children.append(subprocess.Popen(
            [sys.executable, os.path.join(HERE, "loadgen.py"),
             "--url", f"http://127.0.0.1:{session['port']}/queries.json",
             "--traffic", traffic_file,
             "--n-users", str(cfg["n_users"]), "--n-items", str(cfg["n_items"]),
             "--seed", str(seed), "--seconds", str(seconds),
             "--procs", str(procs), "--index", str(i),
             "--start-at", repr(start_at), "--out", out],
            stdout=subprocess.DEVNULL,
            env={k: v for k, v in os.environ.items() if k != "BENCH_RUN"},
        ))
    session["procs"].extend(children)
    session["window_monotonic"] = (
        time.monotonic() + (start_at - time.time()),
        time.monotonic() + (start_at - time.time()) + seconds,
    )
    deadline = start_at + seconds + float(traffic.get("reply_timeout_s", 60)) + 30
    results = []
    try:
        for child, out in zip(children, outs):
            try:
                rc = child.wait(timeout=max(1.0, deadline - time.time()))
            except subprocess.TimeoutExpired:
                rc = -9
            if rc != 0:
                raise RuntimeError(f"generator process exited {rc}")
            with open(out) as f:
                results.append(json.load(f))
            os.remove(out)
    finally:
        for child in children:  # none outlives the call, whatever happened
            if child.poll() is None:
                child.kill()
                child.wait()
    return results


def merge(results: list[dict]) -> dict:
    lat = np.concatenate([np.asarray(r["latencies_ms"], float) for r in results])
    ok = np.concatenate([np.asarray(r["ok_flags"], bool) for r in results])
    return {
        "attempted": int(sum(r["attempted"] for r in results)),
        "failed": int(sum(r["failed"] for r in results)),
        "ok_in_window": int(sum(r["ok_in_window"] for r in results)),
        "window_s": float(results[0]["window_s"]),
        "latencies_ms": lat,
        "ok": ok,
        "sample": [s for r in results for s in r["sample"]],
        "notes": {
            "generator_lateness_ms": [r["lateness_ms"] for r in results],
            "generator_cpu_share": [round(r["generator_cpu_share"], 3)
                                    for r in results],
            "last_reply_after_window_s": max(
                r["last_done_after_window_s"] for r in results),
            "first_failures": [f for r in results for f in r["first_failures"]][:3],
        },
    }


def latency_percentile(lat_ms: np.ndarray, ok: np.ndarray, q: float) -> float:
    """Percentile over ALL queries of the window; one that failed counts as
    slower than any reply."""
    values = np.where(ok, lat_ms, np.inf)
    values.sort()
    i = max(0, int(np.ceil(q * len(values))) - 1)
    return float(values[i])


def warm_http(session: dict, traffic: dict, seed: int) -> None:
    """A short burst of the cell's own traffic through the HTTP path, so the
    window meets warm connections and threads; then no program may compile
    any more."""
    warm = dict(traffic, loop="closed",
                connections=int(traffic.get("connections", 64)))
    run_generators(session, warm, float(traffic.get("warm_seconds", 3.0)),
                   seed + 1, "warm")


def measure(session: dict, ctx: Context) -> dict:
    server = session["server"]
    compiles0 = compiles_so_far()
    batch0 = histogram_state(server, "batch_size")
    wait0 = histogram_state(server, "batch_queue_wait_seconds")
    merged = merge(run_generators(session, ctx.traffic, ctx.seconds, ctx.seed,
                                  "window"))
    batch1 = histogram_state(server, "batch_size")
    wait1 = histogram_state(server, "batch_queue_wait_seconds")
    merged["batches"] = batch1[0] - batch0[0]
    merged["batched_queries"] = batch1[1] - batch0[1]
    merged["queue_wait_mean_s"] = (
        (wait1[1] - wait0[1]) / max(wait1[0] - wait0[0], 1.0))
    merged["compiles_in_window"] = compiles_so_far() - compiles0
    merged["measured_monotonic"] = session["window_monotonic"]
    merged["notes"]["compiles_in_window"] = merged["compiles_in_window"]
    merged["notes"]["batches"] = merged["batches"]
    return merged


def free_program_state(session: dict) -> None:
    """Stop the server and drop the staged slabs, so that the reference has
    the chip to itself."""
    server = session.pop("server", None)
    if server is not None:
        server.stop()
    session.pop("runtime", None)
    model = session.pop("model", None)
    if model is not None:
        model._serving_state = None
    gc.collect()


def compare_sample(ctx: Context, sample: list[dict], tables,
                   failed: int, compiles: float) -> list[Check]:
    """The numbers that decide `correct`, each the worst over the sample."""
    from benchmarks.reference import topk_scores as ref

    limits = ctx.plan.workload["limits"]
    uf, itf = tables
    n_users, n_items = uf.shape[0], itf.shape[0]
    num = int(ctx.traffic.get("num", 10))
    user_rows, served_rows, served_scores, black_rows = [], [], [], []
    malformed = excluded = unsorted = 0
    for s in sample:
        q, items = s["query"], s["reply"].get("item_scores", [])
        rows = [ref.row_of(it.get("item"), "i", n_items) for it in items]
        black = [ref.row_of(b, "i", n_items) for b in q.get("blacklist", [])]
        if (len(rows) != num or min(rows, default=-1) < 0
                or len(set(rows)) != len(rows)):
            malformed += 1
            continue
        excluded += len(set(rows) & set(black))
        scores = [float(it["score"]) for it in items]
        unsorted += int(any(a < b for a, b in zip(scores, scores[1:])))
        user_rows.append(ref.row_of(q["user"], "u", n_users))
        served_rows.append(rows)
        served_scores.append(scores)
        black_rows.append(black)
    score_gap = score_rms = rank_gap = float("nan")
    if user_rows:
        user_rows = np.asarray(user_rows)
        served_rows = np.asarray(served_rows)
        got = np.asarray(served_scores)
        want, scale = ref.served_scores(user_rows, served_rows, uf, itf)
        kth = ref.kth_best_allowed(user_rows, black_rows, num, uf, itf)
        rel = np.abs(got - want) / scale
        score_gap = float(np.max(rel))
        # the widest gap swings by its nature; the root mean square over the
        # same few thousand scores is steady from seed to seed
        score_rms = float(np.sqrt(np.mean(rel ** 2)))
        # how far the worst item served lies, by the reference's own
        # scores, below the reference's num-th best allowed item
        rank_gap = float(np.max(
            (kth - want.min(axis=1)) / scale.max(axis=1)).clip(min=0.0))
    return [
        Check("score_gap", score_gap, limits["score_gap"]),
        Check("score_rms_gap", score_rms, limits["score_rms_gap"]),
        Check("rank_gap", rank_gap, limits["rank_gap"]),
        Check("malformed_replies", float(malformed), 0.0),
        Check("excluded_items_served", float(excluded), 0.0),
        Check("unsorted_replies", float(unsorted), 0.0),
        Check("failed_queries", float(failed), 0.0),
        Check("compiles_in_window", float(compiles), 0.0),
        Check("sample_too_small", float(len(sample) < 10), 0.0),
    ]


def check(session: dict, ctx: Context, win: dict) -> list[Check]:
    tables = session["tables"]
    free_program_state(session)
    t0 = time.monotonic()
    checks = compare_sample(ctx, win["sample"], tables, win["failed"],
                            win["compiles_in_window"])
    ctx.log(f"reference over {len(win['sample'])} sampled replies in "
            f"{time.monotonic() - t0:.1f}s")
    return checks


def teardown(session: dict) -> None:
    for child in session.get("procs", []):
        if child.poll() is None:
            child.kill()
            child.wait()
    free_program_state(session)


def prove(ctx: Context, controls: bool, window) -> dict:
    """One short window of the cell's own traffic against the reference, for
    setting limits; with `controls` also the control — the program's own
    int8 serving slabs (`serve_dtype: "int8"`), the nearest precision the
    program has below the bf16 operands the configuration states — and the
    faults a served answer can have, planted in the sampled replies: an item
    id altered, a score altered."""
    import copy

    def one(serve_dtype):
        session = build_session(ctx, serve_dtype)
        try:
            warm_http(session, ctx.traffic, ctx.seed)
            win = window(session, ctx)
            tables = session["tables"]
            free_program_state(session)
            sample = win["sample"]
            numbers = {c.name: c.value for c in compare_sample(
                ctx, sample, tables, win["failed"], win["compiles_in_window"])}
            return numbers, sample, tables, win["notes"]
        finally:
            teardown(session)

    numbers, sample, tables, notes = one(None)
    out = {"program": numbers, "notes": notes, "sample": len(sample)}
    if controls:
        for name, edit in (("fault_item_altered", _alter_item),
                           ("fault_score_altered", _alter_score)):
            broken = copy.deepcopy(sample)
            edit(broken[ctx.seed % len(broken)], tables[1].shape[0])
            out[name] = {c.name: c.value
                         for c in compare_sample(ctx, broken, tables, 0, 0.0)}
        del tables, sample
        gc.collect()
        out["control_int8"] = one("int8")[0]
    return out


def _alter_item(entry: dict, n_items: int) -> None:
    first = entry["reply"]["item_scores"][0]
    row = int(first["item"][1:])
    taken = {it["item"] for it in entry["reply"]["item_scores"]}
    while f"i{row}" in taken:
        row = (row + 7919) % n_items
    first["item"] = f"i{row}"


def _alter_score(entry: dict, n_items: int) -> None:
    entry["reply"]["item_scores"][0]["score"] *= 1.05
