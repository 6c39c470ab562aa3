"""`dispatch.finish_ms` (PR 38): its reader on a hand-made `Reading` — the
span present, the mean a batch; the span absent, as on the parent commit,
None — its entry in BENCHMARK.json, and a rehearsal in which a real
dispatcher records the span and the line lists the name."""

from __future__ import annotations

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from benchmarks import harness  # noqa: E402
from predictionio_tpu.obs.spans import (  # noqa: E402
    NO_TRACE, Span, get_default_recorder, new_span_id)
from test_benchmark_smoke import BENCHMARK, root_with, run_cli  # noqa: E402
from test_serve_split_metrics import (  # noqa: E402
    HISTORY, SHARDED, STEADY, TRAIN, reader_of, reading)

NAME = "dispatch.finish_ms"

#: a measured window far from any real reading of this machine's clock, and
#: from test_span_metrics.py's and test_serve_split_metrics.py's
W0 = 9.0e8


@pytest.fixture(scope="module")
def finished_window():
    """Three batches that finished in [W0, W0 + 10] in 1, 2 and 6 ms (the
    state spans the dispatcher's worker records), one after the window."""
    rec = get_default_recorder()
    for start, dur in ((1.0, 0.001), (2.0, 0.002), (5.0, 0.006), (12.0, 0.5)):
        rec.record(Span(trace_id=NO_TRACE, span_id=new_span_id(),
                        name="batch.finish", start=1.0, duration=dur,
                        start_mono=W0 + start, attrs={"batch_size": 2}))
        rec.record(Span(trace_id="t-finish", span_id=new_span_id(),
                        name="batch.predict", start=1.0, duration=0.009,
                        start_mono=W0 + start - 0.009))
    return {"measured_monotonic": (W0 + 0.0, W0 + 10.0)}


@pytest.mark.parametrize("cell", [STEADY, SHARDED, HISTORY])
def test_reader_gives_the_mean_a_batch(cell, finished_window):
    assert reader_of(cell, NAME).read(reading(finished_window)) == pytest.approx(
        1000 * (0.001 + 0.002 + 0.006) / 3)


@pytest.mark.parametrize("window", [
    {"measured_monotonic": (W0 + 20.0, W0 + 30.0)},  # no batch finished in it
    {},                                              # a driver with no window
])
def test_reader_gives_none_where_no_batch_finished(window, finished_window):
    assert reader_of(STEADY, NAME).read(reading(window)) is None


@pytest.mark.parametrize("recorder", ["parent", "older"])
def test_reader_gives_none_on_the_parents_spans(recorder, finished_window,
                                                monkeypatch):
    """The parent commit's dispatcher closes on retirement and records no
    `batch.finish` (and an older one's recorder keeps no statistics): the
    metric is left out, nothing raises."""
    from predictionio_tpu.obs import spans

    real = spans.get_default_recorder()

    class Parent:
        def stats(self, *window):
            return {k: v for k, v in real.stats(*window).items()
                    if k != "batch.finish"}

    class Older:
        pass

    stand_in = Parent() if recorder == "parent" else Older()
    monkeypatch.setattr(spans, "get_default_recorder", lambda: stand_in)
    assert reader_of(STEADY, NAME).read(reading(finished_window)) is None


def test_the_metric_has_its_file_its_entry_and_its_cells():
    """(By name, not by place: a later PR appends behind it.)"""
    committed = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    entries = [m for m in committed["per_layer"] if m["name"] == NAME]
    assert entries == [{
        "name": NAME, "unit": "ms/batch", "better": "lower",
        "source": "program_span", "layer": "Dispatcher",
        "moves": "query_p50_ms", "workloads": [STEADY, SHARDED, HISTORY]}]
    assert os.path.exists(os.path.join(
        ROOT, "benchmarks", "layer_metrics", NAME + ".py"))
    # the train cell runs no dispatcher: its plan does not list the metric
    assert NAME not in {m["name"] for m in harness.load_plan(
        ROOT, TRAIN).metrics("per_layer")}


def test_traced_rehearsal_lists_the_name_without_a_number(tmp_path):
    root = root_with(tmp_path, BENCHMARK)
    out = run_cli(["--workload", STEADY, "--seed", "2147485888", "--seconds",
                   "2", "--trace", "1", "--rehearsal"], root=root)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, out.stderr[-3000:]
    assert line["metrics"][NAME]["value"] is None
