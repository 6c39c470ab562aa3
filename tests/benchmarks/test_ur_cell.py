"""The Universal Recommender cell's own pieces (CPU, small): its data from
the seed, the schedule the driver fills the event store from, the operation
and byte counts, each per-layer reader on a recorded window, and runs whose
timed path is broken underneath reading not correct."""

from __future__ import annotations

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from benchmarks import harness, loadgen, roofline, trace_reduce, ur_data, ur_metrics  # noqa: E402
from benchmarks.reference import ur_scores as ref  # noqa: E402
from predictionio_tpu.obs.spans import Span, get_default_recorder, new_span_id  # noqa: E402
from test_benchmark_smoke import BENCHMARK, HERE, root_with, run_script  # noqa: E402

CELL = "ur-taobao-userbehavior.serve-history"
PLAN = harness.load_plan(ROOT, CELL)
CFG = PLAN.config
SMALL = dict(CFG, **CFG["rehearsal"])
NEW = ["ur.history_read_ms", "ur.history_events_per_query", "ur.prepare_ms",
       "ur.device_wait_ms", "ur.decode_ms", "ur.batch_device_ms",
       "ur.score_roofline", "ur.fused_topk_roofline", "ur.step_mfu_pct",
       "device.idle_pct.ur"]
PEAKS = roofline.peaks_for("TPU v5 lite")


# -- the configuration and the cell, as the issue states them -----------------


def test_the_configuration_is_the_sources_shape_and_the_cut_is_written_down():
    assert (CFG["n_users"], CFG["n_items"]) == (987_994, 4_162_024)
    assert CFG["indicators"] == ["buy", "pv", "cart", "fav"]
    assert CFG["algorithm"]["max_correlators_per_item"] == 50
    assert CFG["algorithm"]["max_query_events"] == 100
    assert CFG["architecture"] is None
    assert CFG["resident"]["bytes"] == 4 * 4_162_024 * 50 * 8 == 6_659_238_400
    assert CFG["reduced"] == PLAN.config_entry["reduced"] == ["events_in_store"]
    assert "events_in_store" in CFG
    for key in ("correlators", "history", "num", "ids", "figures", "primary"):
        assert key in CFG["assumed"]
    assert PLAN.cell["chips"] == 1 and PLAN.workload["chips"] == 1


def test_the_cells_traffic_is_what_the_issue_names():
    t = PLAN.workload["traffic"]
    assert (t["loop"], t["generator_procs"], t["connections"]) == ("open", 2, 64)
    assert (t["user_zipf_s"], t["num"]) == (1.0, 20)
    assert (t["blacklist_share"], t["blacklist_max"]) == (0.15, 8)
    assert t["rate_qps"] > 0 and "rate_from" in t
    assert set(PLAN.workload["limits"]) == {"score_gap", "score_rms_gap",
                                             "rank_gap"}
    assert {m["name"] for m in PLAN.metrics("per_layer")} >= set(NEW)
    assert {m["name"] for m in PLAN.metrics("end_to_end")} == {
        "query_p50_ms", "setup_s"}


# -- data from the seed --------------------------------------------------------


def test_correlators_depend_on_the_seed_alone_and_keep_a_trained_tables_shape():
    a = ur_data.make_correlators(SMALL, 2147483659, threads=3)
    b = ur_data.make_correlators(SMALL, 2147483659, threads=8)
    c = ur_data.make_correlators(SMALL, 2147483660)
    n_items, top_n = SMALL["n_items"], 50
    assert len(a) == 4
    for (idx, w), (idx2, w2), (idx3, _w3) in zip(a, b, c):
        assert idx.shape == w.shape == (n_items, top_n)
        assert idx.dtype == np.int32 and w.dtype == np.float32
        assert np.array_equal(idx, idx2) and np.array_equal(w, w2)
        assert not np.array_equal(idx, idx3)
        assert idx.min() == -1 and idx.max() < n_items
        # -1 only in a row's tail, weight 0 there and positive elsewhere
        dead = idx < 0
        assert (dead[:, :-1] <= dead[:, 1:]).all() and not dead[:, :40].any()
        assert 0.07 < dead.mean() < 0.13
        assert (w[dead] == 0).all() and (w[~dead] >= 0.25).all()
    # by popularity: the most listed item stands in far more rows than the
    # median one, and the rarest items are still listed
    counts = np.bincount(a[1][0][a[1][0] >= 0], minlength=n_items)
    assert counts.max() > 8 * np.median(counts) and np.median(counts) > 10


def test_a_users_history_depends_on_seed_and_row_and_is_heavy_tailed():
    h = ur_data.user_history(CFG, 7, 123)
    assert list(h) == CFG["indicators"]
    again = ur_data.user_history(CFG, 7, 123)
    assert all(np.array_equal(h[k], again[k]) for k in h)
    lengths = {k: [] for k in h}
    for row in range(400):
        for k, v in ur_data.user_history(CFG, 11, row).items():
            lengths[k].append(len(v))
            assert v.dtype == np.int64 and (v >= 0).all() and (
                v < CFG["n_items"]).all()
    pv = np.asarray(lengths["pv"])
    # no floor: a share of users has seen a handful of things, or none
    assert pv.min() < 10 and pv.max() <= 400
    assert 60 < pv.mean() < 120 and np.median(pv) < pv.mean()  # heavy tail
    assert (pv > 100).any()  # some users have more than the engine reads
    assert 1.0 < np.mean(lengths["buy"]) < 3.5 < np.mean(lengths["cart"])


def test_history_events_keep_each_types_order_in_time():
    history = {"buy": np.array([5, 6]), "pv": np.array([1, 2, 3]),
               "cart": np.array([], np.int64), "fav": np.array([9])}
    events = ur_data.history_events(CFG, "u7", history)
    assert len(events) == 6
    assert all(e.entity_id == "u7" and e.target_entity_type == "item"
               for e in events)
    pv = sorted((e for e in events if e.event == "pv"),
                key=lambda e: e.event_time)
    assert [e.target_entity_id for e in pv] == ["i1", "i2", "i3"]
    assert len({e.event_time for e in events}) == 6


def test_the_driver_enumerates_the_generators_own_schedule():
    driver = harness.load_module(PLAN, "drivers", PLAN.workload["driver"])
    traffic = dict(PLAN.workload["traffic"], rate_qps=30.0)
    rows = driver.scheduled_users(traffic, CFG, 4.0, 2147483659)
    sent = []
    for index in range(2):
        offsets = loadgen.arrival_times(15.0, 4.0, 2147483659, index)
        sent += [int(q["user"][1:]) for q in loadgen.fixed_mix(
            len(offsets), traffic, CFG["n_users"], CFG["n_items"],
            2147483659, 2000 + index)]
    assert rows == sent and len(rows) == 120
    variant = driver.variant_of(CFG)
    assert variant["engineFactory"].endswith("UniversalRecommenderEngine")
    assert variant["algorithms"][0]["params"] == CFG["algorithm"]


def test_the_control_rounds_weights_to_bfloat16_and_leaves_indices():
    driver = harness.load_module(PLAN, "drivers", PLAN.workload["driver"])
    idx = np.array([[1, -1]], np.int32)
    w = np.array([[1.2345678, 0.0]], np.float32)
    (idx2, w2), = driver.round_weights([(idx, w)], "bfloat16")
    assert idx2 is idx and w2.dtype == np.float32
    assert w2[0, 0] != w[0, 0] and abs(w2[0, 0] - w[0, 0]) < 2 ** -8 * 1.24
    assert w2[0, 1] == 0.0


# -- the reference on a hand-worked case --------------------------------------


def test_the_reference_on_hand_worked_tables():
    idx = np.array([[1, 3, -1], [2, 2, -1], [0, 1, 2], [3, -1, -1]], np.int32)
    w = np.array([[2.0, 1.0, 9.0], [5.0, 0.5, 9.0], [1.0, 1.0, 1.0],
                  [4.0, 9.0, 9.0]], np.float32)
    tables = [(idx, w), (idx, 2 * w)]
    # indicator 0: the latest two of (0, 3, 2) are 3 and 2; indicator 1: 1
    total = ref.scores(tables, [np.array([0, 3, 2]), np.array([1])], depth=2)
    assert total.tolist() == [1.0 + 4.0, 5.5, 1.0 + 2.0, 4.0]
    assert ref.served_scores(tables, [np.array([0, 3, 2]), np.array([1])],
                             2, [3, 0]).tolist() == [4.0, 5.0]
    rows, scores = ref.top(total, dead=[1], num=2)
    assert rows.tolist() == [0, 3] and scores.tolist() == [5.0, 4.0]
    best = ref.best_allowed(
        tables, [[np.array([0, 3, 2]), np.array([1])]] * 2
        + [[np.array([0]), np.array([], np.int64)]] * 2,
        [[1], [], [], [2]], 2, 2)
    # ascending; the last two users score item 2 alone (then nothing): a
    # right answer to them holds one item, and none
    assert best.tolist() == [[4.0, 5.0], [5.0, 5.5], [0.0, 1.0], [0.0, 0.0]]
    assert ref.top(np.zeros(4, np.float32), [], 2)[0].tolist() == []
    assert ref.row_of("i12", "i", 13) == 12
    assert ref.row_of("i13", "i", 13) == ref.row_of("i01", "i", 13) == -1


# -- the check on replies shorter than num, and the driver's own count ---------


class _Store:
    def __init__(self, by_user):
        self.by_user = by_user


def _right_reply(tables, history, black, num, depth=100):
    names = SMALL["indicators"]
    total = ref.scores(tables, [history[n] for n in names], depth)
    dead = set(black) | set(int(i) for i in ref.latest(history[names[0]], depth))
    rows, scores = ref.top(total, dead, num)
    return {"item_scores": [{"item": f"i{int(r)}", "score": float(v)}
                            for r, v in zip(rows, scores)]}


@pytest.fixture(scope="module")
def short_sample():
    """Twelve users' right replies at the rehearsal size: ten drawn as the
    cell draws them, one who has seen one thing (fewer than `num` items
    score) and one who has seen nothing (none does)."""
    driver = harness.load_module(PLAN, "drivers", PLAN.workload["driver"])
    ctx = harness.make_context(PLAN, 77, 1.0, False, True)
    tables = ur_data.make_correlators(ctx.config, 77)
    names = ctx.config["indicators"]
    by_user = {row: ur_data.user_history(ctx.config, 77, row)
               for row in range(10)}
    rare = int(np.argmin(np.bincount(
        tables[1][0][tables[1][0] >= 0], minlength=ctx.config["n_items"])))
    none = np.zeros(0, np.int64)
    by_user[10] = {n: (np.array([rare]) if n == "pv" else none) for n in names}
    by_user[11] = {n: none for n in names}
    sample = [{"query": {"user": f"u{row}", "num": 20,
                         "blacklist": ["i3"] if row % 2 else []},
               "reply": _right_reply(tables, h, [3] if row % 2 else [], 20)}
              for row, h in by_user.items()]
    return driver, ctx, tables, _Store(by_user), sample


def test_a_reply_is_as_long_as_the_reference_says(short_sample):
    """`positive_only`: a short history is answered with the items that
    score, fewer than `num` or none; the check accepts exactly that many
    and refuses a reply cut short or left empty."""
    import copy

    driver, ctx, tables, store, sample = short_sample
    lengths = [len(s["reply"]["item_scores"]) for s in sample]
    assert lengths[:10] == [20] * 10 and 0 < lengths[10] < 20
    assert lengths[11] == 0

    def numbers(replies):
        return {c.name: (c.value, c.ok) for c in driver.compare_sample(
            ctx, replies, tables, store, 0, 0.0, 0.0)}

    right = numbers(sample)
    assert all(ok for _v, ok in right.values()), right
    cut = copy.deepcopy(sample)
    cut[0]["reply"]["item_scores"].pop()  # 19 where 20 score
    cut[10]["reply"]["item_scores"] = []  # nothing where some score
    assert numbers(cut)["malformed_replies"] == (2.0, False)
    padded = copy.deepcopy(sample)  # an item that does not score, served
    padded[11]["reply"]["item_scores"] = [{"item": "i5", "score": 1.0}]
    wrong = numbers(padded)
    assert wrong["malformed_replies"] == (1.0, False)
    assert not wrong["score_gap"][1]


def test_the_driver_counts_the_postings_from_tables_and_histories(short_sample):
    """`postings_named`: for every scheduled query, the slots of each table
    that name a distinct thing of the user's latest 100 of that type —
    counted from the host tables and the inserted histories, against a
    plain comparison of every slot with every history entry."""
    driver, ctx, tables, _store, _sample = short_sample
    rows = driver.scheduled_users(ctx.traffic, ctx.config, ctx.seconds, ctx.seed)
    by_user = {row: ur_data.user_history(ctx.config, 77, row)
               for row in set(rows)}
    session = {"tables": tables, "store": _Store(by_user)}
    want = sum(
        int(np.isin(idx, ref.latest(by_user[row][name], 100)).sum())
        for row in rows
        for name, (idx, _w) in zip(ctx.config["indicators"], tables))
    assert driver.postings_named(session, ctx) == want > 0


# -- operation and byte counts, hand-worked ------------------------------------


def test_ur_counts_on_the_cells_shape():
    assert ur_metrics.table_entries(CFG) == 4 * 50 * 4_162_024
    assert ur_metrics.query_flops(CFG) == 2 * 4 * 50 * 4_162_024
    flops, nbytes = ur_metrics.score_cost(CFG, live_queries=2.0, postings=30_000)
    assert (flops, nbytes) == (30_000, 8 * 30_000 + 2 * 4 * 4_162_024)
    flops, nbytes = ur_metrics.topk_cost(CFG, live_queries=2.0)
    assert (flops, nbytes) == (2 * 4_162_024, 2 * 4 * 4_162_024)
    least, bound = roofline.roofline_seconds(
        *ur_metrics.score_cost(CFG, 1.0, 12_000), PEAKS)
    assert bound == "memory"
    assert abs(least - (96_000 + 16_648_096) / 819e9) < 1e-15


# -- each new reader on a recorded window --------------------------------------

W0 = 4.0e8


def device_trace() -> trace_reduce.TraceSummary:
    """Three batches in a 10 s window: the scoring program 3, 4 and 6 ms, of
    it the tail kernel 0.8, 0.8 and 1.6 (the third batch ran the add-only
    program first, 1 ms); another program 5 ms."""
    us = 1_000
    ops, mods = [], []
    for start, lead, run, tail in ((1000, 0, 3000, 800), (3000, 0, 4000, 800),
                                   (6000, 1000, 6000, 1600)):
        at = start * 1000 * us
        if lead:
            mods.append([f"jit__accumulate_jit({at})", at, lead * us])
            ops.append(["%scatter.9 = f32[33357824] scatter(...)", at, lead * us])
            at += lead * us
        mods.append([f"jit__score_topk_jit({at})", at, run * us])
        ops.append(["%scatter.3 = f32[33357824] scatter(...)", at,
                    (run - tail) * us])
        ops.append(["%fused_masked_topk.1 = (f32[8,64], s32[8,64]) "
                    "custom-call(...)", at + (run - tail) * us, tail * us])
    mods.append(["jit_other(7)", 8_000_000 * us, 5000 * us])
    ops.append(["%copy.1 = f32[8] copy(...)", 8_000_000 * us, 5000 * us])
    return trace_reduce.reduce_trace({"planes": [{
        "name": "/device:TPU:0",
        "lines": [{"name": "XLA Ops", "events": ops},
                  {"name": "XLA Modules", "events": mods}]}]}, window_s=10.0)


@pytest.fixture(scope="module")
def recorded():
    """The device trace above beside the program's spans of three batches
    (and one after the window) and the driver's counts: 3 batches of 6 live
    queries in all, 480 history events, 90,000 postings."""
    rec = get_default_recorder()

    def put(name, start, dur, parent=None, span_id=None):
        rec.record(Span(trace_id="t-ur-metrics", name=name, start=1.0,
                        span_id=span_id or new_span_id(),
                        parent_span_id=parent, duration=dur,
                        start_mono=W0 + start))

    for start in (1.0, 3.0, 6.0, 12.0):
        root = new_span_id()
        put("ur.history_read", start, 0.003, parent=root)
        put("ur.predict.prepare", start, 0.004, span_id=root)
        put("ur.predict.device", start + 0.004, 0.030)
        put("ur.predict.decode", start + 0.034, 0.0002)
    window = {"measured_monotonic": (W0, W0 + 10.0), "batches": 3.0,
              "batched_queries": 6.0, "history_events": 480,
              "postings_named": 90_000}
    return harness.Reading(config=CFG, workload=PLAN.workload,
                           device_kind="TPU v5 lite", peaks=PEAKS,
                           window=window, trace=device_trace())


def expect(name: str) -> float:
    totals_s = 2.0 * 4 * 4_162_024 / 819e9  # two live queries a batch
    return {
        "ur.history_read_ms": 3.0,
        "ur.history_events_per_query": 80.0,
        "ur.prepare_ms": 4.0,
        "ur.device_wait_ms": 30.0,
        "ur.decode_ms": 0.2,
        "ur.batch_device_ms": 14.0 / 3,  # 3, 4 and 6 + 1 of add-only
        "ur.score_roofline": 100 * 3 * (8 * 30_000 / 819e9 + totals_s) / (
            0.013 + 0.001 - 0.0032),
        "ur.fused_topk_roofline": 100 * 3 * totals_s / 0.0032,
        "ur.step_mfu_pct": 100 * 6 * 2 * 4 * 50 * 4_162_024 / (0.019 * 197e12),
        "device.idle_pct.ur": 100 * (1 - 0.019 / 10.0),
    }[name]


@pytest.mark.parametrize("name", NEW)
def test_reader_on_a_recorded_window(name, recorded):
    reader = harness.load_module(PLAN, "layer_metrics", name)
    assert reader.read(recorded) == pytest.approx(expect(name), rel=1e-9)
    assert reader.__doc__ and reader.read(recorded) <= (
        100.0 if name.endswith(("roofline", "_pct", ".ur")) else 1e9)


def test_a_trace_that_ends_early_is_scaled_to_the_part_it_covers(recorded):
    """The profiler keeps a bounded number of device events: where the trace
    holds three of the window's six batches, the busy seconds and the
    queries are those of half the window; a time a batch stays what it was."""
    import dataclasses

    half = dataclasses.replace(recorded, window=dict(
        recorded.window, batches=6.0, batched_queries=12.0))
    assert ur_metrics.covered_share(recorded) == 1.0
    assert ur_metrics.covered_share(half) == 0.5
    read = {name: harness.load_module(PLAN, "layer_metrics", name).read
            for name in NEW}
    assert read["device.idle_pct.ur"](half) == pytest.approx(
        100 * (1 - 2 * 0.019 / 10.0))
    assert read["ur.step_mfu_pct"](half) == pytest.approx(expect("ur.step_mfu_pct"))
    assert read["ur.batch_device_ms"](half) == pytest.approx(14.0 / 3)
    # the same mean batch (2 live queries, 15,000 postings), three runs of it
    assert read["ur.score_roofline"](half) == pytest.approx(
        100 * 3 * (8 * 15_000 / 819e9 + 2.0 * 4 * 4_162_024 / 819e9) / (
            0.013 + 0.001 - 0.0032))
    assert read["ur.fused_topk_roofline"](half) == pytest.approx(
        expect("ur.fused_topk_roofline"))


@pytest.mark.parametrize("name", NEW)
def test_reader_finds_nothing_where_the_program_has_nothing(name, recorded):
    """On the parent's program (no `ur.*` span, no counter, another
    program's trace) and without a trace, a reader returns None."""
    import dataclasses

    other = trace_reduce.reduce_trace({"planes": [{
        "name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": [["%copy.1 = f32[8] copy(...)", 0, 9]]},
            {"name": "XLA Modules", "events": [["jit_other(7)", 0, 9]]}]}]},
        window_s=1.0)
    empty = dataclasses.replace(
        recorded, trace=None if name.startswith("device.") else other,
        window={"measured_monotonic": (W0 + 50.0, W0 + 60.0), "batches": 3.0,
                "batched_queries": 6.0})
    reader = harness.load_module(PLAN, "layer_metrics", name)
    assert reader.read(empty) is None
    assert reader.read(dataclasses.replace(empty, trace=None, window={})) is None


# -- `correct` can come out false ----------------------------------------------


@pytest.mark.parametrize("fault,failing", [
    ("item_altered", "rank_gap"),
    ("reply_dropped", "failed_queries"),
    ("history_dropped", "score_gap"),
    ("store_read_fails", "history_read_failures"),
])
def test_a_broken_ur_path_reads_not_correct(fault, failing, tmp_path):
    """The whole run, but for the look for a chip, with the program's timed
    path broken underneath (tests/benchmarks/ur_fault_runner.py plants it)."""
    root = root_with(tmp_path, BENCHMARK)
    out = run_script(
        os.path.join(HERE, "ur_fault_runner.py"),
        [fault, "--workload", CELL, "--seed", "77", "--seconds", "1",
         "--trace", "0", "--rehearsal"], root)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is False, line["checks"]
    assert not line["checks"][failing]["ok"], line["checks"]
