"""The Universal Recommender train cell's own pieces (CPU, small): the
configuration as the issue states it, its entries in BENCHMARK.json, the
benchmark's own count of the join's work, and each new reader on a recorded
window — a number where the program has the span or the trace has the
program, None where not (the parent commit)."""

from __future__ import annotations

import os
import sys
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import harness, roofline, trace_reduce, ur_train_data, ur_train_metrics  # noqa: E402
from benchmarks.reference import ur_cco as ref  # noqa: E402

CELL = "ur-taobao-userbehavior-train.train-cco"
PLAN = harness.load_plan(ROOT, CELL)
CFG = PLAN.config
SMALL = dict(CFG, **CFG["rehearsal"])
NEW = ["ur_train.group_s", "ur_train.downsample_s", "ur_train.join_s",
       "ur_train.llr_topn_s", "ur_train.join_roofline",
       "ur_train.step_mfu_pct", "device.idle_pct.urtrain"]
APPENDED = ["train_job_s", "train.algo_stage_s", "train.persist_s",
            "train.unattributed_s"]
PEAKS = roofline.peaks_for("TPU v5 lite")


def test_the_configuration_is_the_whole_data_set_and_nothing_is_cut():
    from predictionio_tpu.models import cco

    assert (CFG["n_users"], CFG["n_items"]) == (987_994, 4_162_024)
    assert CFG["n_behaviours"] == 100_150_807
    assert CFG["indicators"] == ["buy", "pv", "cart", "fav"]
    assert CFG["architecture"] is None
    assert CFG["reduced"] == PLAN.config_entry["reduced"] == []
    algo = CFG["algorithm"]
    assert (algo["max_correlators_per_item"],
            algo["max_events_per_event_type"]) == (50, 500)
    assert CFG["downsampling"]["seed"] == cco.DOWNSAMPLE_SEED == 0xDEADBEEF
    totals = ur_train_data.type_totals(CFG)
    assert sum(totals.values()) == CFG["n_behaviours"]
    assert totals["pv"] == 89_745_528 and totals["buy"] == 2_001_015
    for key in ("figures", "primary", "events", "n_users"):
        assert key in CFG["assumed"]
    assert PLAN.cell["chips"] == 1


def test_the_cell_and_its_metrics_are_appended():
    committed = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    assert committed["workloads"][-1]["name"] == CELL
    assert committed["configs"][-1]["name"] == CFG["name"]
    assert [m["name"] for m in committed["per_layer"]][-len(NEW):] == NEW
    by_name = {m["name"]: m for m in
               committed["end_to_end"] + committed["per_layer"]}
    for name in APPENDED:
        assert by_name[name]["workloads"][-1] == CELL, name
    for name in NEW:
        m = by_name[name]
        assert m["workloads"] == [CELL] and m["moves"] == "train_job_s"
        assert os.path.exists(os.path.join(
            ROOT, "benchmarks", "layer_metrics", name + ".py"))
    assert {m["name"] for m in PLAN.metrics("end_to_end")} == {
        "train_job_s", "setup_s"}
    assert set(NEW) <= {m["name"] for m in PLAN.metrics("per_layer")}


def test_the_events_depend_on_the_seed_alone():
    a = ur_train_data.make_events(SMALL, 2147483659)
    b = ur_train_data.make_events(SMALL, 2147483659)
    c = ur_train_data.make_events(SMALL, 5)
    for name in CFG["indicators"]:
        assert all(np.array_equal(x, y) for x, y in zip(a[name], b[name]))
        assert a[name][0].size == c[name][0].size  # the same work, any seed
        assert not np.array_equal(a[name][0], c[name][0])
        assert a[name][1].max() < SMALL["n_items"]


def test_the_benchmarks_count_of_the_join_is_the_programs():
    """At a small size the driver's own count of pairs and distinct pairs
    equals what the program's join saw (its span attributes)."""
    from predictionio_tpu.models import cco

    events = ur_train_data.make_events(SMALL, 11)
    work = ref.join_work(ref.Reference(SMALL, events))
    cap = SMALL["algorithm"]["max_events_per_event_type"]
    kept = {
        name: cco.downsample(cco.group_by_user(
            *events[name], SMALL["n_users"], SMALL["n_items"]),
            cap, cco.DOWNSAMPLE_SEED, m)
        for m, name in enumerate(CFG["indicators"])}
    _tables, stats = cco.join_indicators(
        kept["buy"], [kept[n] for n in CFG["indicators"]], SMALL["n_users"],
        50, self_first=True)
    for name, pairs in zip(CFG["indicators"], stats["pairs"]):
        assert work[name]["kept"] == kept[name].cols.size
        # the program lays out the self indicator's diagonal too (a buy
        # with itself, one a kept buy) and drops it on the device
        diagonal = kept["buy"].cols.size if name == "buy" else 0
        assert work[name]["pairs"] + diagonal == pairs
    assert sum(w["distinct"] for w in work.values()) == sum(
        b["distinct"] for b in stats["blocks"])


def test_join_bytes_and_operations_on_hand_worked_numbers():
    work = {"buy": {"kept": 10, "pairs": 6, "distinct": 4},
            "pv": {"kept": 100, "pairs": 50, "distinct": 30}}
    # pairs 8 B in and 8 B sorted out; counts 4 B; the events, expanded on
    # the host, not at all
    assert ur_train_metrics.join_least_bytes(work) == (
        16 * 6 + 4 * 4 + 16 * 50 + 4 * 30)
    assert ur_train_metrics.train_ops(work) == 6 + 16 * 4 + 50 + 16 * 30


def window(spans=True):
    timings = {"job": 10.0, "unattributed": 0.5, "train": 8.0,
               "persist": 1.5}
    if spans:
        timings.update({"ur.train.group": 2.0, "ur.train.downsample": 0.5,
                        "ur.train.join": 3.0, "ur.train.llr_topn": 1.5})
    return {"jobs": [{"seconds": 10.5, "stage_timings": dict(timings)}] * 2,
            "pairs": {"buy": {"kept": 1000, "pairs": 600, "distinct": 400},
                      "pv": {"kept": 9000, "pairs": 5000, "distinct": 4000}}}


def summary(programs):
    return trace_reduce.TraceSummary(
        window_s=30.0, busy_s=6.0, n_devices=1, program_runs=programs)


def reading(win, trace):
    return harness.Reading(CFG, PLAN.workload, "TPU v5 lite", PEAKS, win,
                           trace)


@pytest.mark.parametrize("name,want", [
    ("ur_train.group_s", 2.0), ("ur_train.downsample_s", 0.5),
    ("ur_train.join_s", 3.0), ("ur_train.llr_topn_s", 1.5),
    ("train.algo_stage_s", 8.0), ("train.persist_s", 1.5),
    ("train.unattributed_s", 1.0)])
def test_span_readers_where_the_span_is_and_where_not(name, want):
    reader = harness.load_module(PLAN, "layer_metrics", name)
    assert reader.read(reading(window(), None)) == pytest.approx(want)
    if name.startswith("ur_train."):
        assert reader.read(reading(window(spans=False), None)) is None


def test_device_readers_on_a_recorded_window():
    runs = {"_pair_counts_jit": [0.5, 0.1, 0.5, 0.1],
            "_llr_topn_jit": [0.4, 0.1, 0.4, 0.1]}
    got = harness.load_module(PLAN, "layer_metrics", "ur_train.join_roofline")
    least = 2 * ur_train_metrics.join_least_bytes(window()["pairs"]) / 819e9
    assert got.read(reading(window(), summary(runs))) == pytest.approx(
        100 * least / 1.2)
    mfu = harness.load_module(PLAN, "layer_metrics", "ur_train.step_mfu_pct")
    ops = 2 * ur_train_metrics.train_ops(window()["pairs"])
    assert mfu.read(reading(window(), summary(runs))) == pytest.approx(
        100 * ops / (2.2 * 197e12))
    # busy 6 s of two 10.5 s jobs in a 30 s window: the read-backs are out
    idle = harness.load_module(PLAN, "layer_metrics", "device.idle_pct.urtrain")
    assert idle.read(reading(window(), summary(runs))) == pytest.approx(
        100 * 15 / 21)
    assert idle.read(reading(dict(window(), jobs=[]), summary(runs))) is None
    assert idle.read(reading(window(), None)) is None
    # the parent: no join program in the trace, no work counted, no trace
    for name in ("ur_train.join_roofline", "ur_train.step_mfu_pct"):
        reader = harness.load_module(PLAN, "layer_metrics", name)
        assert reader.read(reading(window(), summary({}))) is None
        assert reader.read(reading(dict(window(), pairs=None),
                                   summary(runs))) is None
        assert reader.read(reading(window(), None)) is None


def test_a_program_without_the_sparse_path_fails_at_once(monkeypatch):
    """The parent's URAlgorithm.train asks numpy for 16 TB; the driver
    refuses before it makes a single event."""
    from benchmarks.drivers import ur_train_jobs
    from predictionio_tpu import models

    monkeypatch.setitem(sys.modules, "predictionio_tpu.models.cco",
                        types.ModuleType("predictionio_tpu.models.cco"))
    monkeypatch.setattr(models, "cco", sys.modules["predictionio_tpu.models.cco"],
                        raising=False)
    with pytest.raises(harness.BenchmarkError, match="sparse"):
        ur_train_jobs.require_sparse_train()


def test_a_wrong_table_reads_not_correct():
    """The comparison on planted faults: a thing that is no candidate, the
    diagonal kept, a row cut short."""
    from benchmarks.drivers import ur_train_jobs

    events = ur_train_data.make_events(SMALL, 19)
    reference = ref.Reference(SMALL, events)
    items = ref.sample_items(events, SMALL, 19, top=20, uniform=80)
    rows = ur_train_jobs.reference_rows(reference, items)
    tables = ref.reference_tables(reference, items)
    ctx = types.SimpleNamespace(plan=PLAN, config=SMALL)

    def model(served, diagonal=0):
        return {"names": list(CFG["indicators"]), "items": SMALL["n_items"],
                "shapes_ok": True, "served": served, "diagonal": diagonal}

    def failed(m):
        return [c.name for c in ur_train_jobs.compare(ctx, [m], rows)
                if not c.ok]

    assert failed(model(tables)) == []
    assert failed(model(tables, diagonal=3)) == ["cco_diagonal"]
    items_, idx, sc = tables["pv"]
    wrong = idx.copy()
    full = np.flatnonzero(wrong[:, 0] >= 0)[0]
    wrong[full, 0] = (wrong[full, 0] + 1) % SMALL["n_items"]
    while wrong[full, 0] in idx[full]:
        wrong[full, 0] = (wrong[full, 0] + 1) % SMALL["n_items"]
    assert "cco_score_gap" in failed(model(dict(tables, pv=(items_, wrong, sc))))
    short = idx.copy()
    short[full, (short[full] >= 0).sum() - 1] = -1
    assert "cco_count_gap" in failed(model(dict(tables, pv=(items_, short, sc))))
