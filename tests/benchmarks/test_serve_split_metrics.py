"""The per-layer metrics that read the serving path's split spans (PR 37):
each reader on a hand-made `Reading` — spans present, the arithmetic; spans
absent, None — and a rehearsal that lists the names."""

from __future__ import annotations

import json
import os
import sys
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from benchmarks import harness  # noqa: E402
from predictionio_tpu.obs.spans import (  # noqa: E402
    NO_TRACE, Span, get_default_recorder, new_span_id)
from test_benchmark_smoke import BENCHMARK, root_with, run_cli  # noqa: E402

TRAIN = "als-netflix-implicit-r10.train-batch"
STEADY = "als-webgraph-de-d128.serve-steady"
SHARDED = "als-webgraph-desparse-d128.serve-sharded"
HISTORY = "ur-taobao-userbehavior.serve-history"

#: a measured window far from any real reading of this machine's clock, and
#: from test_span_metrics.py's
W0 = 6.0e8


def reading(window: dict, trace=None) -> harness.Reading:
    return harness.Reading(config={}, workload={}, device_kind="cpu",
                           peaks=None, window=window, trace=trace)


@pytest.fixture(scope="module")
def served_window():
    """Two batches, one of them of the Universal Recommender's shape and one
    of the sharded tier's, three requests and 4 s with no query held in the
    window [W0, W0 + 10]; one batch and one request after it."""
    rec = get_default_recorder()

    def put(name, start, dur, parent=None, span_id=None, trace="t-split"):
        rec.record(Span(trace_id=trace, name=name, start=1.0,
                        span_id=span_id or new_span_id(),
                        parent_span_id=parent, duration=dur,
                        start_mono=W0 + start))

    for start in (1.0, 5.0, 12.0):
        batch, device = new_span_id(), new_span_id()
        put("als.predict.put", start + 0.0002, 0.0004, parent=device)
        put("device.launch", start + 0.001, 0.0006, parent=device)
        put("device.wait", start + 0.002, 0.004, parent=device)
        put("als.predict.copy_back", start + 0.006, 0.0015, parent=device)
        put("als.predict.device", start, 0.008, parent=batch, span_id=device)
        put("batch.predict", start, 0.009, span_id=batch)
    # the second batch ran a second program and two more copies back
    put("device.launch", 5.02, 0.0004)
    put("device.wait", 5.03, 0.002)
    put("sharded.copy_back", 5.04, 0.0010)
    put("ur.predict.copy_back", 5.05, 0.0005)
    put("sharded.dispatch.put", 5.06, 0.0016)
    put("ur.predict.put", 5.07, 0.0002)
    ur = new_span_id()
    put("ur.predict.copy_back", 5.081, 0.001, parent=ur)
    put("ur.predict.device", 5.08, 0.003, span_id=ur)
    for start in (1.0, 2.0, 5.0, 12.0):
        root = new_span_id()
        put("query.decode", start, 0.001, parent=root)
        put("batch.queue_wait", start + 0.001, 0.006, parent=root)
        put("batch.pickup", start + 0.001, 0.001, parent=root)
        put("batch.assemble", start + 0.002, 0.003, parent=root)
        put("batch.slot_wait", start + 0.005, 0.002, parent=root)
        put("batch.device_dispatch", start + 0.007, 0.009, parent=root)
        put("batch.result_transfer", start + 0.0165, 0.0005, parent=root)
        put("query.wake", start + 0.017, 0.0008, parent=root)
        put("query.wait", start + 0.001, 0.0175, parent=root)
        put("query.encode", start + 0.0185, 0.0005, parent=root)
        put("server.request", start, 0.020, span_id=root)
    for start in (0.5, 2.5, 6.0, 8.0):
        put("dispatch.no_work", start, 1.0, trace=NO_TRACE)
    put("dispatch.no_work", 13.0, 1.0, trace=NO_TRACE)
    return {"measured_monotonic": (W0 + 0.0, W0 + 10.0),
            "latencies_ms": np.array([21.5, 22.5, 23.5, 500.0]),
            "ok": np.array([True, True, True, False])}


#: 2 batches in the window
SERVE_CASES = [
    ("dispatch.pickup_ms", 1.0),
    ("dispatch.assemble_ms", 3.0),
    ("dispatch.slot_wait_ms", 2.0),
    ("dispatch.wake_ms", 0.8),
    ("predict.put_ms", 1000 * (2 * 0.0004 + 0.0016 + 0.0002) / 2),
    ("predict.launch_ms", 1000 * (2 * 0.0006 + 0.0004) / 2),
    ("predict.ready_wait_ms", 1000 * (2 * 0.004 + 0.002) / 2),
    ("predict.copy_back_ms", 1000 * (2 * 0.0015 + 0.0010 + 0.0015) / 2),
    # what als.predict.device / ur.predict.device hold beside their children
    ("predict.device_unattributed_ms",
     1000 * (2 * (0.008 - 0.0004 - 0.0006 - 0.004 - 0.0015) + 0.002) / 2),
    # server.request 20 ms, of it self 20 - 1 - 17.5 - 0.5 = 1, the parts
    # decode 1 + encode 0.5 + queue 6 + dispatch 9 + transfer 0.5 + wake 0.8
    ("serve.unattributed_ms", 20.0 - 1.0 - 17.8),
    ("http.outside_server_ms", 22.5 - 20.0),
    ("dispatch.no_work_pct", 40.0),
]


def reader_of(cell: str, name: str):
    plan = harness.load_plan(ROOT, cell)
    assert name in {m["name"] for m in plan.metrics("per_layer")}
    return harness.load_module(plan, "layer_metrics", name)


@pytest.mark.parametrize("cell", [STEADY, SHARDED, HISTORY])
@pytest.mark.parametrize("name,expect", SERVE_CASES)
def test_reader_gives_the_arithmetic_where_the_spans_are_and_none_where_not(
        name, expect, cell, served_window):
    read = reader_of(cell, name).read
    assert read(reading(served_window)) == pytest.approx(expect, rel=1e-4)
    # a window in which no such span ended, and a driver with no window
    empty = dict(served_window, measured_monotonic=(W0 + 20.0, W0 + 30.0))
    assert read(reading(empty)) is None
    assert read(reading({})) is None


def test_the_three_hand_offs_sum_to_the_queue_wait(served_window):
    from benchmarks.span_metrics import mean_ms

    parts = sum(reader_of(STEADY, n).read(reading(served_window)) for n in (
        "dispatch.pickup_ms", "dispatch.assemble_ms", "dispatch.slot_wait_ms"))
    assert parts == pytest.approx(
        mean_ms(reading(served_window), "batch.queue_wait"))


def test_readers_return_none_on_the_parents_spans(served_window, monkeypatch):
    """The parent commit records none of the new spans (and an older one's
    recorder keeps no statistics): every metric is left out, nothing
    raises."""
    from predictionio_tpu.obs import spans

    old = ("server.request", "query.decode", "query.encode", "query.wait",
           "batch.queue_wait", "batch.device_dispatch", "batch.predict",
           "batch.result_transfer",
           # under their older meanings: first arrival -> dispatch, and a
           # device-wait span with nothing inside it
           "batch.assemble", "als.predict.device")
    real = spans.get_default_recorder()

    class Parent:
        def stats(self, *window):
            return {k: v for k, v in real.stats(*window).items() if k in old}

    class Older:
        pass

    for recorder in (Parent(), Older()):
        monkeypatch.setattr(spans, "get_default_recorder", lambda r=recorder: r)
        for name, _ in SERVE_CASES:
            if name == "http.outside_server_ms" and isinstance(recorder, Parent):
                continue  # reads a span the parent has: it reports there
            assert reader_of(STEADY, name).read(reading(served_window)) is None
        assert reader_of(STEADY, "device.idle_with_work_pct").read(
            reading(served_window, trace=_trace(0.3))) is None


def _trace(idle_share: float, ur_runs: int = 0):
    runs = {"jit__score_topk_jit": [0.001] * ur_runs} if ur_runs else {}
    return types.SimpleNamespace(
        idle_share=idle_share, window_s=10.0, busy_s=10.0 * (1 - idle_share),
        program_runs=runs)


def test_idle_with_work_is_the_cells_idle_less_no_work(served_window):
    read = reader_of(STEADY, "device.idle_with_work_pct").read
    assert read(reading(served_window)) is None  # an untraced run
    assert read(reading(served_window, trace=_trace(0.85))) == pytest.approx(
        85.0 - 40.0)
    # the Universal Recommender's cell: its own reader scales by the share
    # of the window's batches the trace holds (here 5 of 10: busy 3 s of
    # the 5 s covered)
    ur = dict(served_window, batches=10)
    assert reader_of(HISTORY, "device.idle_with_work_pct").read(
        reading(ur, trace=_trace(0.7, ur_runs=5))) == pytest.approx(
        100.0 * (1 - 3.0 / 5.0) - 40.0)


JOBS = [{"seconds": 5.0, "stage_timings": {
    "job": 4.9, "unattributed": 0.02, "als.train.dense_eligible": 2.4,
    "als.train.pair_key": 0.9, "als.train.pair_sort": 1.0 + 0.1 * i,
    "als.train.pair_group": 0.4}} for i in range(3)]


def test_pair_sort_reader():
    read = reader_of(TRAIN, "train.pair_sort_s").read
    assert read(reading({"jobs": JOBS})) == pytest.approx(1.1)
    assert read(reading({"jobs": [{"seconds": 5.0, "stage_timings": {
        "als.train.dense_eligible": 2.4}}]})) is None
    assert read(reading({})) is None


def test_every_new_metric_has_a_file_an_entry_and_its_cells():
    committed = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    entries = {m["name"]: m for m in committed["per_layer"]}
    serving = [n for n, _ in SERVE_CASES] + ["device.idle_with_work_pct"]
    for name in serving:
        m = entries[name]
        assert m["workloads"] == [STEADY, SHARDED, HISTORY]
        assert m["moves"] == "query_p50_ms" and m["better"] == "lower"
        assert os.path.exists(os.path.join(
            ROOT, "benchmarks", "layer_metrics", name + ".py"))
    assert entries["http.outside_server_ms"]["source"] == "host_clock"
    assert entries["device.idle_with_work_pct"]["source"] == "device_trace"
    assert entries["train.pair_sort_s"]["workloads"] == [TRAIN]
    assert entries["train.pair_sort_s"]["moves"] == "train_job_s"
    # appended: nothing that was there moved behind a new entry
    names = [m["name"] for m in committed["per_layer"]]
    first_new = min(names.index(n) for n in serving + ["train.pair_sort_s"])
    assert set(names[first_new:]) == set(serving + ["train.pair_sort_s"])


@pytest.mark.parametrize("cell,new", [
    (TRAIN, ["train.pair_sort_s"]),
    (STEADY, [n for n, _ in SERVE_CASES]),
])
def test_traced_rehearsal_lists_the_new_names_without_numbers(cell, new, tmp_path):
    root = root_with(tmp_path, BENCHMARK)
    out = run_cli(["--workload", cell, "--seed", "2147484777", "--seconds", "2",
                   "--trace", "1", "--rehearsal"], root=root)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, out.stderr[-3000:]
    for name in new:
        assert line["metrics"][name]["value"] is None, name
