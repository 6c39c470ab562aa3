"""The per-layer metrics that read the program's own spans (PR 25): each
reader on a hand-made `Reading`, the unchanged trace reducer on a trace whose
host plane holds span-named events, and a rehearsal that lists the names."""

from __future__ import annotations

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from benchmarks import harness, trace_reduce  # noqa: E402
from predictionio_tpu.obs.spans import Span, get_default_recorder, new_span_id  # noqa: E402
from test_benchmark_smoke import BENCHMARK, root_with, run_cli  # noqa: E402

TRAIN = "als-netflix-implicit-r10.train-batch"
STEADY = "als-webgraph-de-d128.serve-steady"

#: two jobs as `drivers/train_jobs.py` hands them through; the second lacks
#: nothing, so every mean is over both
JOBS = [
    {"seconds": 25.0, "stage_timings": {
        "read": 0.2, "prepare": 0.1, "train": 24.0, "persist": 0.5,
        "job": 24.9, "unattributed": 0.3, "train.algorithm": 23.9,
        "als.train.degrees": 8.0, "als.train.dense_eligible": 5.0,
        "als.stage.host_prep": 3.0, "als.stage.transfer": 0.6,
        "als.stage.densify": 5.6, "als.train.program": 1.3,
        "als.train.copy_back": 0.05, "persist.serialize": 0.3,
        "persist.write": 0.1}},
    {"seconds": 27.0, "stage_timings": {
        "read": 0.2, "prepare": 0.1, "train": 26.0, "persist": 0.7,
        "job": 26.8, "unattributed": 0.5, "train.algorithm": 25.9,
        "als.train.degrees": 9.0, "als.train.dense_eligible": 5.0,
        "als.stage.host_prep": 4.0, "als.stage.transfer": 0.8,
        "als.stage.densify": 5.8, "als.train.program": 1.5,
        "als.train.copy_back": 0.05, "persist.serialize": 0.4,
        "persist.write": 0.2}},
]
#: what the parent commit's jobs carry: the four stage keys and no more
PARENT_JOBS = [{"seconds": 25.0, "stage_timings": {
    "read": 0.2, "prepare": 0.1, "train": 24.0}}]

#: a measured window far from any real reading of this machine's clock
W0 = 3.0e8


def reading(window: dict) -> harness.Reading:
    return harness.Reading(config={}, workload={}, device_kind="cpu",
                           peaks=None, window=window, trace=None)


@pytest.fixture(scope="module")
def served_window():
    """Three batches and four requests in the window [W0, W0 + 10], and one
    batch after it, in the process's recorder as the server's spans are."""
    rec = get_default_recorder()

    def put(name, start, dur, parent=None, span_id=None):
        rec.record(Span(trace_id="t-span-metrics", name=name, start=1.0,
                        span_id=span_id or new_span_id(),
                        parent_span_id=parent, duration=dur,
                        start_mono=W0 + start))

    for i, start in enumerate((1.0, 3.0, 5.0, 12.0)):
        put("als.predict.prepare", start, 0.002)
        put("als.predict.device", start + 0.002, 0.012)
        put("als.predict.decode", start + 0.014, 0.4 + 0.1 * i)
        put("batch.predict", start, 0.42 + 0.1 * i)
    for start in (1.0, 2.0, 3.0, 4.0):
        root = new_span_id()
        put("query.decode", start, 0.01, parent=root)
        put("batch.queue_wait", start + 0.01, 0.05, parent=root)
        put("query.wait", start + 0.01, 0.6, parent=root)
        put("query.encode", start + 0.61, 0.02, parent=root)
        put("server.request", start, 0.7, span_id=root)
    return {"measured_monotonic": (W0 + 0.0, W0 + 10.0)}


TRAIN_CASES = [
    ("train.host_prep_s", (16.0 + 18.0) / 2),
    ("train.transfer_s", 0.7),
    ("train.densify_s", 5.7),
    ("train.program_s", 1.4),
    ("train.persist_s", 0.6),
    ("train.unattributed_s", ((25.0 - 24.9 + 0.3) + (27.0 - 26.8 + 0.5)) / 2),
]
SERVE_CASES = [
    ("dispatch.batch_service_ms", 1000 * (0.42 + 0.52 + 0.62) / 3),
    ("predict.prepare_ms", 2.0),
    ("predict.device_wait_ms", 12.0),
    ("predict.decode_ms", 500.0),
    ("http.request_self_ms", 1000 * (0.7 - 0.01 - 0.6 - 0.02)),
]


def reader_of(cell: str, name: str):
    plan = harness.load_plan(ROOT, cell)
    assert name in {m["name"] for m in plan.metrics("per_layer")}
    return harness.load_module(plan, "layer_metrics", name)


@pytest.mark.parametrize("name,expect", TRAIN_CASES)
def test_train_reader_gives_a_number_where_the_span_is_and_none_where_not(
        name, expect):
    read = reader_of(TRAIN, name).read
    assert read(reading({"jobs": JOBS})) == pytest.approx(expect)
    assert read(reading({"jobs": PARENT_JOBS})) is None
    assert read(reading({"jobs": []})) is None
    assert read(reading({})) is None


@pytest.mark.parametrize("name,expect", SERVE_CASES)
def test_serving_reader_gives_a_number_where_the_span_is_and_none_where_not(
        name, expect, served_window):
    read = reader_of(STEADY, name).read
    assert read(reading(served_window)) == pytest.approx(expect, rel=1e-4)
    # a window in which no such span ended, and a driver with no window
    assert read(reading({"measured_monotonic": (W0 + 20.0, W0 + 30.0)})) is None
    assert read(reading({})) is None


def test_serving_readers_return_none_on_a_recorder_without_stats(
        served_window, monkeypatch):
    """The parent commit's recorder keeps no statistics: the metric is left
    out of the line, nothing raises."""
    from predictionio_tpu.obs import spans

    class Old:
        pass

    monkeypatch.setattr(spans, "get_default_recorder", lambda: Old())
    for name, _ in SERVE_CASES:
        assert reader_of(STEADY, name).read(reading(served_window)) is None


def test_eleven_span_metrics_are_appended_and_nothing_else_changed():
    names = [m["name"] for m in BENCHMARK["per_layer"]
             if m["source"] == "program_span"]
    new = [n for n, _ in TRAIN_CASES + SERVE_CASES]
    assert set(new) <= set(names) and len(new) == 11
    committed = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    assert [m["name"] for m in committed["per_layer"]][-11:] == new
    for m in committed["per_layer"][-11:]:
        assert m["better"] == "lower" and m["source"] == "program_span"
        assert len(m["workloads"]) == 1


def test_reduce_trace_names_idle_gaps_by_program_span():
    """With the spans on the profiler's /host:CPU plane the unchanged
    reducer puts each device-idle gap down to the innermost span."""
    dev = trace_reduce.DEVICE_PLANE_PREFIX + "0"
    ms = 1_000_000
    trace = {"planes": [
        {"name": dev, "lines": [
            {"name": trace_reduce.OPS_LINE, "events": [
                ["fusion.1", 0, 10 * ms], ["fusion.2", 510 * ms, 10 * ms]]},
            {"name": trace_reduce.MODULES_LINE, "events": [
                ["jit__serve_recommend_jit(1)", 0, 10 * ms],
                ["jit__serve_recommend_jit(1)", 510 * ms, 10 * ms]]},
        ]},
        {"name": trace_reduce.HOST_PLANE, "lines": [
            {"name": "query-batch_0", "events": [
                ["batch.predict", 0, 480 * ms],
                ["als.predict.device", 0, 12 * ms],
                ["als.predict.decode", 12 * ms, 468 * ms],
                ["als.predict.vocab_inverse", 12 * ms, 400 * ms],
                ["batch.serve", 480 * ms, 20 * ms]]},
            {"name": "Thread-7", "events": [
                ["query.wait", 0, 700 * ms]]},
        ]},
    ]}
    s = trace_reduce.reduce_trace(trace, window_s=0.520)
    gaps = dict(s.idle_gaps)
    # 16 instants of the one 500 ms gap: the copy covers 12..412 ms of it,
    # the rest of the decode up to 480, the serve loop up to 500; the
    # request that waits all along is never the innermost
    assert gaps == pytest.approx({
        "als.predict.vocab_inverse": 0.5 * 13 / 16,
        "als.predict.decode": 0.5 * 2 / 16,
        "batch.serve": 0.5 * 1 / 16,
    })
    assert [g[0] for g in s.breakdown()["idle_gaps"]][0] == \
        "als.predict.vocab_inverse"


@pytest.mark.parametrize("cell,new", [
    (TRAIN, [n for n, _ in TRAIN_CASES]),
    (STEADY, [n for n, _ in SERVE_CASES]),
])
def test_traced_rehearsal_lists_the_new_names_without_numbers(cell, new, tmp_path):
    root = root_with(tmp_path, BENCHMARK)
    out = run_cli(["--workload", cell, "--seed", "2147483777", "--seconds", "2",
                   "--trace", "1", "--rehearsal"], root=root)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, out.stderr[-3000:]
    for name in new:
        assert line["metrics"][name]["value"] is None, name
