"""Driver `http_open_loop_history`: the client's wait on the Universal
Recommender, every query's history read live from the event store.

`http_open_loop`'s open loop, generator processes and percentiles as they
are (`benchmarks/serving.py` and `loadgen.py` by import), over another
engine family: a real `QueryServer` on a local port in the process that
holds the chip, its `EngineRuntime` built by `build_runtime`'s own steps
from an in-memory `URModel` whose correlator tables are made from `--seed`
(`benchmarks/ur_data.py`), queries through `_BatchDispatcher` ->
`URAlgorithm.batch_predict` -> `cco.batch_score_topk`. The event store is
the `memory` backend, filled through its own insert path with the WHOLE
history of every user the warm-up's and the window's schedules name — the
schedules are functions of the seed (`loadgen.arrival_times`, `fixed_mix`),
so set-up enumerates them; a window with another seed or rate (the sweep's)
fills what it lacks before its generators start.

`check` compares ~300 sampled replies with `benchmarks/reference/
ur_scores.py` at the full catalogue and holds the program's counters at 0:
compiles in the window, history reads the store failed.
"""

from __future__ import annotations

import copy
import datetime as dt
import gc
import time

import numpy as np

from benchmarks import loadgen, serving, ur_data
from benchmarks.harness import BenchmarkError, Check, Context

FACTORY = "predictionio_tpu.engines.universal.UniversalRecommenderEngine"
#: the program's counters the window is read around (the process-wide
#: registry); a program without them gives none and the readers leave
#: their metrics out
COUNTERS = ("ur_batches_total", "ur_exclusion_bytes_total",
            "ur_history_read_failures_total")

rehearsal_env = serving.rehearsal_env


def variant_of(cfg: dict) -> dict:
    """The engine variant (what an engine.json holds) of the configuration."""
    algo = dict(cfg["algorithm"])
    return {
        "id": cfg["name"],
        "engineFactory": FACTORY,
        "datasource": {"params": {"app_name": algo["app_name"],
                                  "indicators": list(cfg["indicators"])}},
        "algorithms": [{"name": "ur", "params": algo}],
    }


def scheduled_users(traffic: dict, cfg: dict, seconds: float, seed: int) -> list:
    """User rows of every query the open loop will send in a window of
    `seconds` with `seed`: the generators' own schedule, made here too."""
    procs = int(traffic.get("generator_procs", 2))
    rows = []
    for index in range(procs):
        offsets = loadgen.arrival_times(
            float(traffic["rate_qps"]) / procs, seconds, seed, index)
        for q in loadgen.fixed_mix(len(offsets), traffic, cfg["n_users"],
                                   cfg["n_items"], seed, 2000 + index):
            rows.append(int(q["user"][1:]))
    return rows


def round_weights(tables: list, dtype_name: str) -> list:
    """The tables with their weights rounded to `dtype_name` and back: the
    control (bfloat16 is the nearest precision below the float32 the
    configuration states)."""
    import ml_dtypes

    dtype = getattr(ml_dtypes, dtype_name)
    return [(idx, w.astype(dtype).astype(np.float32)) for idx, w in tables]


def require_staged_tables() -> None:
    """A program that cannot stage the correlator tables once (before PR 35:
    it padded and copied them in every batch and built a (B, n_items) host
    mask beside them) cannot run this configuration: say so at once, before
    anything is made, instead of dying in the allocator minutes in."""
    try:
        from predictionio_tpu.models.resident import ResidentCorrelators  # noqa: F401
    except ImportError:
        raise BenchmarkError(
            "this program has no staged correlator state "
            "(models/resident.ResidentCorrelators): it cannot serve the "
            "Universal Recommender at this catalogue") from None


def build_session(ctx: Context, weights_as: str | None = None) -> dict:
    require_staged_tables()
    from predictionio_tpu.controller.engine import resolve_engine
    from predictionio_tpu.controller.params import load_symbol
    from predictionio_tpu.core.base import RuntimeContext
    from predictionio_tpu.data.storage.base import App, EngineInstance
    from predictionio_tpu.data.storage.registry import (
        SourceConfig,
        Storage,
        StorageConfig,
    )
    from predictionio_tpu.data.store.bimap import BiMap
    from predictionio_tpu.engines.universal.engine import IndicatorModel, URModel
    from predictionio_tpu.obs.jaxmon import ensure_compile_listener
    from predictionio_tpu.workflow.server import (
        EngineRuntime,
        QueryServer,
        QueryServerConfig,
    )

    cfg = ctx.config
    t0 = time.monotonic()
    tables = ur_data.make_correlators(cfg, ctx.seed)
    ctx.log(f"correlator tables made in {time.monotonic() - t0:.1f}s")
    served = round_weights(tables, weights_as) if weights_as else tables
    t0 = time.monotonic()
    key = ("i", cfg["n_items"])
    if key not in serving._VOCABS:
        serving._VOCABS[key] = BiMap(
            {f"i{i}": i for i in range(cfg["n_items"])})
    vocab = serving._VOCABS[key]
    ctx.log(f"item vocabulary made in {time.monotonic() - t0:.1f}s")
    # every indicator's targets are items: one catalogue, one vocabulary
    model = URModel(
        item_vocab=vocab,
        indicator_models=[
            IndicatorModel(name=name, correlator_scores=w, correlator_idx=idx,
                           target_vocab=vocab)
            for name, (idx, w) in zip(cfg["indicators"], served)],
        primary_indicator=cfg["indicators"][0],
    )

    storage = Storage(StorageConfig(
        sources={"MEM": SourceConfig("MEM", "memory", {})},
        repositories={"METADATA": "MEM", "EVENTDATA": "MEM",
                      "MODELDATA": "MEM"},
    ))
    app_id = storage.get_meta_data_apps().insert(
        App(id=0, name=cfg["algorithm"]["app_name"]))
    storage.get_events().init_app(app_id)
    store = ur_data.HistoryStore(cfg, ctx.seed, storage, app_id)

    variant = variant_of(cfg)
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
    serving_step = engine.make_serving(engine_params)
    serving_ctx = RuntimeContext(storage=storage, mode="serve")
    t0 = time.monotonic()
    for a in algorithms:
        a.set_serving_context(serving_ctx)
        a.warmup(model)  # stages the tables and runs every bucket's programs
    ctx.log(f"staged and warmed up in {time.monotonic() - t0:.1f}s")
    runtime = EngineRuntime(
        instance=instance, engine=engine, engine_params=engine_params,
        algorithms=algorithms, models=[model], serving=serving_step,
        query_class=algorithms[0].query_class(),
        query_serializer=algorithms[0].query_serializer(),
    )
    server = QueryServer(storage, runtime,
                         QueryServerConfig(ip="127.0.0.1", port=0))
    port = server.start()
    return {
        "server": server, "port": port, "runtime": runtime, "model": model,
        "tables": tables, "store": store, "ctx": ctx, "procs": [],
        "span_sums": bridge_span_sums(),
    }


def bridge_span_sums() -> dict:
    """Sum the `events` of every `ur.history_read` span as it completes
    (the recorder's bridge: the span is the one observation). A program
    without the span leaves the sum at 0 and the reader leaves its metric
    out."""
    from predictionio_tpu.obs.spans import get_default_recorder

    sums = {"events": 0}

    def observe(sp):
        sums["events"] += int(sp.attrs.get("events", 0))

    get_default_recorder().bridge("ur.history_read", observe)
    return {"sums": sums, "callbacks": {"ur.history_read": observe}}


def postings_named(session: dict, ctx: Context) -> int:
    """Postings the window's queries name, counted from the configuration's
    tables and the inserted histories alone — whatever the program reads,
    plans or skips: for every scheduled query and indicator, each DISTINCT
    thing among the user's latest `max_query_events` targets, the number of
    correlator slots of that indicator's table that name it."""
    from benchmarks.reference import ur_scores as ref

    cfg = ctx.config
    if "list_lengths" not in session:
        n_items = int(cfg["n_items"])
        lengths = []
        for idx, _w in session["tables"]:
            count = np.zeros(n_items + 1, np.int64)  # bin 0: the -1 slots
            for lo in range(0, n_items, ref.BLOCK):
                count += np.bincount(
                    idx[lo:lo + ref.BLOCK].reshape(-1).astype(np.int64) + 1,
                    minlength=n_items + 1)
            lengths.append(count[1:])
        session["list_lengths"] = lengths
    depth = int(cfg["algorithm"]["max_query_events"])
    by_user = session["store"].by_user
    a_query = {}
    total = 0
    for row in scheduled_users(ctx.traffic, cfg, ctx.seconds, ctx.seed):
        if row not in a_query:
            a_query[row] = sum(
                int(lengths[np.unique(ref.latest(by_user[row][name], depth))].sum())
                for name, lengths in zip(cfg["indicators"],
                                         session["list_lengths"]))
        total += a_query[row]
    return total


def ensure_histories(session: dict, traffic: dict, seconds: float,
                     seed: int) -> None:
    ctx: Context = session["ctx"]
    t0 = time.monotonic()
    store: ur_data.HistoryStore = session["store"]
    added = store.ensure(scheduled_users(traffic, ctx.config, seconds, seed))
    if added:
        ctx.log(f"event store: {added} events inserted in "
                f"{time.monotonic() - t0:.1f}s ({len(store.by_user)} users, "
                f"{store.n_events} events in all)")


def warm_http(session: dict, traffic: dict, seed: int) -> None:
    """A short stretch of the cell's own traffic through the HTTP path, so
    that the window meets warm connections and threads (the closed loop of
    `serving.warm_http` draws users that no schedule names)."""
    seconds = float(traffic.get("warm_seconds", 3.0))
    ensure_histories(session, traffic, seconds, seed + 1)
    serving.run_generators(session, traffic, seconds, seed + 1, "warm")


def setup(ctx: Context) -> dict:
    session = build_session(ctx)
    # the window's users too, so that filling the store is set-up
    ensure_histories(session, ctx.traffic, ctx.seconds, ctx.seed)
    warm_http(session, ctx.traffic, ctx.seed)
    return session


def counters() -> dict | None:
    """{name: total, "by_form": {...}} of the program's counters, or None
    where the program has none (the parent has not)."""
    from predictionio_tpu.obs.registry import get_default_registry

    families = {f.name: f for f in get_default_registry().families()}
    if not all(name in families for name in COUNTERS):
        return None
    out = {name: float(families[name].total) for name in COUNTERS}
    out["by_form"] = {
        form: float(families["ur_batches_total"].value(form=form))
        for form in ("none", "rows", "mask")}
    return out


def window(session: dict, ctx: Context) -> dict:
    ensure_histories(session, ctx.traffic, ctx.seconds, ctx.seed)
    before = counters()
    sums = session["span_sums"]["sums"]
    sums0 = dict(sums)
    win = serving.measure(session, ctx)
    after = counters()
    win["history_events"] = sums["events"] - sums0["events"]
    if ctx.trace:  # the roofline's byte count: a traced run's metric
        win["postings_named"] = postings_named(session, ctx)
    lat, ok = win["latencies_ms"], win["ok"]
    win["end_to_end"] = {
        "query_p50_ms": serving.latency_percentile(lat, ok, 0.50),
        "query_p99_ms": serving.latency_percentile(lat, ok, 0.99),
    }
    win["notes"].update(win["end_to_end"])
    win["notes"]["achieved_qps"] = win["ok_in_window"] / win["window_s"]
    win["notes"]["store_events"] = session["store"].n_events
    win["notes"]["store_users"] = len(session["store"].by_user)
    if before is not None and after is not None:
        win["ur_batches"] = after["ur_batches_total"] - before["ur_batches_total"]
        win["ur_exclusion_bytes"] = (after["ur_exclusion_bytes_total"]
                                     - before["ur_exclusion_bytes_total"])
        win["history_read_failures"] = (
            after["ur_history_read_failures_total"]
            - before["ur_history_read_failures_total"])
        win["notes"]["ur_batches_by_form"] = {
            form: after["by_form"][form] - before["by_form"][form]
            for form in after["by_form"]}
    return win


def free_program_state(session: dict) -> None:
    """Stop the server and drop the staged tables, so that the reference
    has the chip to itself."""
    bridged = session.pop("span_sums", None)
    if bridged is not None:
        from predictionio_tpu.obs.spans import get_default_recorder

        for name, observe in bridged["callbacks"].items():
            get_default_recorder().unbridge(name, observe)
    server = session.pop("server", None)
    if server is not None:
        server.stop()
    session.pop("runtime", None)
    model = session.pop("model", None)
    if model is not None:
        model.resident.drop()
    gc.collect()


def compare_sample(ctx: Context, sample: list, tables: list, store,
                   failed: int, compiles: float, read_failures: float,
                   drop_indicator: str | None = None) -> list[Check]:
    """The numbers that decide `correct`, each the worst over the sample.
    `drop_indicator` is the planted fault: the reference is given the
    histories without that indicator's."""
    from benchmarks.reference import ur_scores as ref

    cfg = ctx.config
    limits = ctx.plan.workload["limits"]
    names = list(cfg["indicators"])
    depth = int(cfg["algorithm"]["max_query_events"])
    n_users, n_items = cfg["n_users"], cfg["n_items"]
    num = int(ctx.traffic.get("num", 20))
    malformed = excluded = unsorted = unknown = 0
    histories, dead, served, got = [], [], [], []
    for s in sample:
        q, items = s["query"], s["reply"].get("item_scores", [])
        rows = [ref.row_of(it.get("item"), "i", n_items) for it in items]
        user = ref.row_of(q["user"], "u", n_users)
        if (len(rows) > num or min(rows, default=0) < 0
                or len(set(rows)) != len(rows)):
            malformed += 1
            continue
        if user not in store.by_user:
            unknown += 1  # a query no schedule named: nothing to compare
            continue
        history = store.by_user[user]
        per_indicator = [
            history[name][:0] if name == drop_indicator else history[name]
            for name in names]
        black = [ref.row_of(b, "i", n_items) for b in q.get("blacklist", [])]
        not_allowed = set(black) | set(
            int(i) for i in ref.latest(history[names[0]], depth))
        excluded += len(set(rows) & not_allowed)
        scores = [float(it["score"]) for it in items]
        unsorted += int(any(a < b for a, b in zip(scores, scores[1:])))
        histories.append(per_indicator)
        dead.append(not_allowed)
        served.append(rows)
        got.append(scores)
    score_gap = score_rms = rank_gap = float("nan")
    rel, below = [], []
    if served:
        best = ref.best_allowed(tables, histories, dead, num, depth)
        for rows, scores, h, top in zip(served, got, histories, best):
            # a right answer holds every allowed item the reference scores
            # above 0, up to num: fewer only where fewer score (a short or
            # empty history), never because a read or a row was lost
            if len(rows) != int((top > 0).sum()):
                malformed += 1
            if not rows:
                continue
            have = np.asarray(scores, np.float64)
            want = ref.served_scores(tables, h, depth, rows).astype(np.float64)
            scale = np.maximum(np.maximum(np.abs(want), np.abs(have)), 1e-30)
            rel.append(np.abs(have - want) / scale)
            # how far the worst item served lies, by the reference's own
            # scores, below the reference's num-th best allowed item (0
            # where fewer than num score above 0)
            below.append(max(float(top[0] - want.min())
                             / max(float(top[0]), 1e-30), 0.0))
    if rel:
        rel = np.concatenate(rel)
        score_gap = float(np.max(rel))
        score_rms = float(np.sqrt(np.mean(rel ** 2)))
        rank_gap = max(below)
    return [
        Check("score_gap", score_gap, limits["score_gap"]),
        Check("score_rms_gap", score_rms, limits["score_rms_gap"]),
        Check("rank_gap", rank_gap, limits["rank_gap"]),
        Check("malformed_replies", float(malformed), 0.0),
        Check("excluded_items_served", float(excluded), 0.0),
        Check("unsorted_replies", float(unsorted), 0.0),
        Check("unscheduled_users", float(unknown), 0.0),
        Check("failed_queries", float(failed), 0.0),
        Check("compiles_in_window", float(compiles), 0.0),
        Check("history_read_failures", float(read_failures), 0.0),
        Check("sample_too_small", float(len(served) < 10), 0.0),
    ]


def check(session: dict, ctx: Context, win: dict) -> list[Check]:
    tables, store = session["tables"], session["store"]
    free_program_state(session)
    t0 = time.monotonic()
    checks = compare_sample(
        ctx, win["sample"], tables, store, win["failed"],
        win["compiles_in_window"],
        # a program that does not count its failed reads cannot be held
        # to none: the check reads as failed
        win.get("history_read_failures", float("nan")))
    ctx.log(f"reference over {len(win['sample'])} sampled replies in "
            f"{time.monotonic() - t0:.1f}s")
    return checks


def teardown(session: dict) -> None:
    for child in session.get("procs", []):
        if child.poll() is None:
            child.kill()
            child.wait()
    free_program_state(session)


def prove(ctx: Context, controls: bool) -> dict:
    """One short window of the cell's own traffic against the reference, for
    setting limits; with `controls` also the control — the same path serving
    tables whose weights were rounded to bfloat16 before staging — and two
    planted faults: an item id altered in a sampled reply, and one
    indicator's history dropped (planted on the reference's side: the
    replies then hold that indicator's hits and the reference does not)."""

    def one(weights_as):
        session = build_session(ctx, weights_as)
        try:
            ensure_histories(session, ctx.traffic, ctx.seconds, ctx.seed)
            warm_http(session, ctx.traffic, ctx.seed)
            win = window(session, ctx)
            tables, store = session["tables"], session["store"]
            free_program_state(session)
            numbers = {c.name: c.value for c in compare_sample(
                ctx, win["sample"], tables, store, win["failed"],
                win["compiles_in_window"],
                win.get("history_read_failures", float("nan")))}
            return numbers, win["sample"], tables, store, win["notes"]
        finally:
            teardown(session)

    numbers, sample, tables, store, notes = one(None)
    out = {"program": numbers, "notes": notes, "sample": len(sample)}
    if controls:
        broken = copy.deepcopy(sample)
        _alter_item(broken[ctx.seed % len(broken)], ctx.config["n_items"])
        out["fault_item_altered"] = {c.name: c.value for c in compare_sample(
            ctx, broken, tables, store, 0, 0.0, 0.0)}
        dropped = ctx.plan.workload["faults"]["history_dropped"]
        out["fault_history_dropped"] = {
            c.name: c.value for c in compare_sample(
                ctx, sample, tables, store, 0, 0.0, 0.0,
                drop_indicator=dropped)}
        del tables, sample, store
        gc.collect()
        out["control_bfloat16"] = one("bfloat16")[0]
    return out


def _alter_item(entry: dict, n_items: int) -> None:
    first = entry["reply"]["item_scores"][0]
    row = int(first["item"][1:])
    taken = {it["item"] for it in entry["reply"]["item_scores"]}
    while f"i{row}" in taken:
        row = (row + 7919) % n_items
    first["item"] = f"i{row}"
