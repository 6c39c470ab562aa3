"""Span recorder (ISSUE 2): hierarchy + context propagation, tail-based
sampling retention rules, thread-safety under concurrent traces, the
span→metric bridge, Perfetto export validity, and the route-label
cardinality guard."""

import json
import threading

import pytest

from predictionio_tpu.obs.registry import MetricsRegistry
from predictionio_tpu.obs.spans import Span, SpanRecorder, new_span_id
from predictionio_tpu.obs.tracing import trace_context


@pytest.fixture()
def recorder():
    return SpanRecorder(max_traces=16, slow_ms=10_000, sample_rate=1.0)


def test_hierarchy_and_trace_context(recorder):
    with trace_context("t-1"):
        with recorder.span("root", server="x") as root:
            with recorder.span("child") as child:
                with recorder.span("grandchild") as grand:
                    pass
            with recorder.span("sibling") as sib:
                pass
    spans = {s.name: s for s in recorder.get_trace("t-1")}
    assert set(spans) == {"root", "child", "grandchild", "sibling"}
    assert root.trace_id == "t-1"
    assert spans["root"].parent_span_id is None
    assert spans["child"].parent_span_id == root.span_id
    assert spans["grandchild"].parent_span_id == child.span_id
    assert spans["sibling"].parent_span_id == root.span_id
    assert grand.duration >= 0 and sib.duration >= 0
    summary = recorder.summaries()[0]
    assert summary["trace_id"] == "t-1"
    assert summary["root"] == "root"
    assert summary["spans"] == 4


def test_span_without_trace_mints_one(recorder):
    with recorder.span("lonely") as sp:
        pass
    assert sp.trace_id
    assert recorder.get_trace(sp.trace_id)[0].name == "lonely"


def test_explicit_trace_id_flows_to_children(recorder):
    """A span opened with trace_id=... must establish trace context for
    everything nested, exactly like an inherited ambient trace."""
    with recorder.span("root", trace_id="t-explicit") as root:
        with recorder.span("child") as child:
            pass
    assert child.trace_id == "t-explicit"
    assert child.parent_span_id == root.span_id
    assert len(recorder.get_trace("t-explicit")) == 2


def test_error_marks_span_and_reraises(recorder):
    with pytest.raises(ValueError):
        with recorder.span("boom", trace_id="t-err"):
            raise ValueError("nope")
    spans = recorder.get_trace("t-err")
    assert spans and spans[0].error
    assert recorder.summaries()[0]["error"]


# -- tail-based sampling ----------------------------------------------------


def test_tail_sampling_drops_boring_keeps_error_and_slow():
    rec = SpanRecorder(max_traces=16, slow_ms=50, sample_rate=0.0)
    # boring: fast, no error, sample_rate 0 → dropped
    with rec.span("fast", trace_id="t-boring"):
        pass
    assert rec.get_trace("t-boring") == []
    # errored → always kept
    with pytest.raises(RuntimeError):
        with rec.span("fails", trace_id="t-err"):
            raise RuntimeError("x")
    assert rec.summaries()[0]["kept"] == "error"
    # slow (≥ slow_ms, via a manually recorded duration) → always kept,
    # even when the SLOW span is a child and the root itself is fast
    rec.record(Span(
        trace_id="t-slow", span_id=new_span_id(), name="slow.child",
        start=0.0, duration=0.120,
    ))
    rec.record(Span(
        trace_id="t-slow", span_id=new_span_id(), name="root",
        start=0.0, duration=0.001,
    ), finalize=True)
    kept = {s["trace_id"]: s for s in rec.summaries()}
    assert kept["t-slow"]["kept"] == "slow"
    assert "t-boring" not in kept


def test_retention_cap_evicts_oldest():
    rec = SpanRecorder(max_traces=4, slow_ms=10_000, sample_rate=1.0)
    for i in range(10):
        with rec.span("r", trace_id=f"t-{i}"):
            pass
    kept = [s["trace_id"] for s in rec.summaries()]
    assert len(kept) == 4
    assert set(kept) == {"t-6", "t-7", "t-8", "t-9"}  # oldest evicted


def test_reused_trace_id_is_capped_and_still_ages_out():
    """X-Request-ID is client-controlled: one id replayed forever must
    neither grow a retained trace unbounded nor pin it against
    eviction."""
    rec = SpanRecorder(max_traces=4, slow_ms=10_000, sample_rate=1.0)
    rec.max_spans_per_trace = 10
    with rec.span("r", trace_id="t-pinned"):
        pass
    for _ in range(50):  # replayed id: merge path
        with rec.span("r", trace_id="t-pinned"):
            pass
    assert len(rec.get_trace("t-pinned")) == 10  # capped
    for i in range(4):  # fresh traces evict the pinned one despite merges
        with rec.span("r", trace_id=f"t-new-{i}"):
            pass
    assert rec.get_trace("t-pinned") == []


def test_unbridge_only_removes_own_callback(recorder):
    reg = MetricsRegistry()
    h1 = reg.histogram("h1_seconds", "")
    h2 = reg.histogram("h2_seconds", "")
    cb1 = lambda sp: h1.observe(sp.duration)  # noqa: E731
    cb2 = lambda sp: h2.observe(sp.duration)  # noqa: E731
    recorder.bridge("x", cb1)
    recorder.bridge("x", cb2)  # newer server wins
    recorder.unbridge("x", cb1)  # stale server's teardown: no-op
    with recorder.span("x", trace_id="t-u1"):
        pass
    assert h2.count == 1 and h1.count == 0
    recorder.unbridge("x", cb2)
    with recorder.span("x", trace_id="t-u2"):
        pass
    assert h2.count == 1  # removed


def test_remote_rooted_fragment_defers_instead_of_dropping():
    """Two servers in one process: the inner daemon's server span (which
    has a REMOTE parent) finalizes mid-request. With sampling that would
    drop it, the fragment must be deferred — not discarded — so the
    outer request's eventual slow/error keep decision sees the full
    union, queue/assemble spans included."""
    rec = SpanRecorder(max_traces=16, slow_ms=100, sample_rate=0.0)
    rec.record(Span(
        trace_id="t-d", span_id="early", parent_span_id="root-id",
        name="batch.queue_wait", start=0.0, duration=0.001,
    ))
    rec.record(Span(
        trace_id="t-d", span_id="daemon", parent_span_id="rpc-id",
        name="server.request", start=0.0, duration=0.001,
    ), finalize=True)
    assert rec.get_trace("t-d") == []  # deferred, not retained yet
    rec.record(Span(  # true root (no parent at all), slow → keep union
        trace_id="t-d", span_id="root-id", parent_span_id=None,
        name="server.request", start=0.0, duration=0.5,
    ), finalize=True)
    assert {s.span_id for s in rec.get_trace("t-d")} == {
        "early", "daemon", "root-id",
    }
    assert rec.summaries()[0]["kept"] == "slow"
    # a TRUE-rooted boring trace still drops definitively
    rec.record(Span(
        trace_id="t-gone", span_id="r2", parent_span_id=None,
        name="storage.rpc", start=0.0, duration=0.001,
    ), finalize=True)
    assert rec.get_trace("t-gone") == []


def test_late_fragment_merges_into_kept_trace(recorder):
    """Cross-process shape: the remote fragment finalizes first, the
    client span arrives after — it must join the kept trace, not strand
    in the active map."""
    with recorder.span("server.request", trace_id="t-m"):
        pass
    recorder.record(Span(
        trace_id="t-m", span_id=new_span_id(), name="storage.rpc",
        start=0.0, duration=0.002,
    ))
    assert {s.name for s in recorder.get_trace("t-m")} == {
        "server.request", "storage.rpc",
    }


# -- concurrency ------------------------------------------------------------


def test_concurrent_traces_no_cross_request_leakage():
    """Hammer the recorder from N threads, each running M sequential
    traces with nested spans (the keep-alive handler-thread shape):
    every trace must keep exactly its own spans with correct parent
    links, and no span may leak into a sibling thread's trace."""
    rec = SpanRecorder(max_traces=1000, slow_ms=10_000, sample_rate=1.0)
    n_threads, n_traces = 8, 25
    errors: list[str] = []

    def worker(w: int) -> None:
        for i in range(n_traces):
            tid = f"t-{w}-{i}"
            with trace_context(tid):
                with rec.span("root", worker=w, i=i) as root:
                    with rec.span("mid") as mid:
                        with rec.span("leaf"):
                            pass
                if root.trace_id != tid or mid.trace_id != tid:
                    errors.append(f"{tid}: wrong trace id")

    threads = [
        threading.Thread(target=worker, args=(w,)) for w in range(n_threads)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors
    for w in range(n_threads):
        for i in range(n_traces):
            tid = f"t-{w}-{i}"
            spans = {s.name: s for s in rec.get_trace(tid)}
            assert set(spans) == {"root", "mid", "leaf"}, (tid, spans)
            assert all(s.trace_id == tid for s in spans.values())
            assert spans["root"].parent_span_id is None
            assert spans["mid"].parent_span_id == spans["root"].span_id
            assert spans["leaf"].parent_span_id == spans["mid"].span_id
            assert spans["root"].attrs == {"worker": w, "i": i}


# -- metric bridge ----------------------------------------------------------


def test_metric_bridge_feeds_histogram(recorder):
    reg = MetricsRegistry()
    hist = reg.histogram("bridged_seconds", "from spans")
    recorder.bridge("stage.x", lambda sp: hist.observe(sp.duration))
    for _ in range(3):
        with recorder.span("stage.x", trace_id="t-b"):
            pass
    with recorder.span("stage.other", trace_id="t-b2"):
        pass
    assert hist.count == 3  # only the declared name feeds it
    assert hist.sum >= 0


def test_bridge_exception_never_breaks_recording(recorder):
    def bad(sp):
        raise RuntimeError("metrics hiccup")

    recorder.bridge("fragile", bad)
    with recorder.span("fragile", trace_id="t-f"):
        pass
    assert recorder.get_trace("t-f")  # span recorded despite bridge error


# -- perfetto export --------------------------------------------------------


def test_perfetto_export_is_valid_chrome_trace_json(recorder):
    with trace_context("t-p"):
        with recorder.span("server.request", server="query", path="/q") as r:
            with recorder.span("batch.device_dispatch", server="query"):
                with recorder.span(
                    "storage.rpc", server="storage-client", dao="events"
                ):
                    pass
    export = recorder.perfetto_export("t-p")
    # round-trips as JSON and has the Chrome trace-event shape
    parsed = json.loads(json.dumps(export))
    events = parsed["traceEvents"]
    assert events
    x_events = [e for e in events if e["ph"] == "X"]
    assert len(x_events) == 3
    for e in events:
        assert e["ph"] in ("X", "M")
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
        if e["ph"] == "X":
            assert isinstance(e["ts"], (int, float))
            assert isinstance(e["dur"], (int, float))
            assert e["args"]["trace_id"] == "t-p"
    # span depth maps to tid so children nest under parents
    by_name = {e["name"]: e for e in x_events}
    assert by_name["server.request"]["tid"] == 0
    assert by_name["batch.device_dispatch"]["tid"] == 1
    assert by_name["storage.rpc"]["tid"] == 2
    # each originating server gets a named process row
    procs = {e["args"]["name"] for e in events if e["ph"] == "M"}
    assert {"query", "storage-client"} <= procs
    assert by_name["server.request"]["args"]["span_id"] == r.span_id


def test_perfetto_export_all_and_missing(recorder):
    assert recorder.perfetto_export("nope")["traceEvents"] == []
    with recorder.span("a", trace_id="t-1"):
        pass
    with recorder.span("b", trace_id="t-2"):
        pass
    events = recorder.perfetto_export()["traceEvents"]
    assert {e["args"]["trace_id"] for e in events if e["ph"] == "X"} == {
        "t-1", "t-2",
    }


# -- route-label cardinality guard (satellite) ------------------------------


def test_route_label_cardinality_bounded():
    """Replay a scan of distinct per-entity paths and assert the metric
    label set stays bounded: every id/name segment collapses."""
    from predictionio_tpu.utils.http import JsonHandler

    label = lambda p: JsonHandler._route_label(None, p)  # noqa: E731
    paths = []
    for i in range(50):
        paths += [
            f"/events/ev-{i}.json",
            f"/events/ev-{i}",
            f"/engine_instances/inst-{i}.html",
            f"/engine_instances/inst-{i}.json",
            f"/engine_instances/inst-{i}",
            f"/cmd/app/app-{i}",
            f"/cmd/app/app-{i}/data",
            f"/cmd/channel/ch-{i}",
            f"/cmd/accesskey/key-{i}",
            f"/tenants/tenant-{i}",
            f"/tenants/tenant-{i}/queries.json",
            f"/tenants/tenant-{i}/rollout/start",
            f"/tenants/tenant-{i}/quota",
        ]
    labels = {label(p) for p in paths}
    assert labels == {
        "/events/{id}.json",
        "/events/{id}",
        "/engine_instances/{id}.html",
        "/engine_instances/{id}.json",
        "/engine_instances/{id}",
        "/cmd/app/{name}",
        "/cmd/app/{name}/data",
        "/cmd/channel/{name}",
        "/cmd/accesskey/{name}",
        "/tenants/{id}",
        "/tenants/{id}/queries.json",
        "/tenants/{id}/rollout/start",
        "/tenants/{id}/quota",
    }
    # non-entity routes pass through untouched
    assert label("/queries.json") == "/queries.json"
    assert label("/cmd/app") == "/cmd/app"
    assert label("/metrics") == "/metrics"


# -- one clock, windowed statistics, the per-job collector (ISSUE 25) ---------


def _after_the_fact(recorder, name, start_mono, duration, parent=None,
                    span_id=None, trace_id="t-stats"):
    """A span built from known instants, as the dispatcher builds its
    per-query spans."""
    sp = Span(trace_id=trace_id, span_id=span_id or new_span_id(), name=name,
              parent_span_id=parent, start=1.0, duration=duration,
              start_mono=start_mono)
    recorder.record(sp)
    return sp


def test_span_stamps_the_monotonic_clock_beside_the_epoch(recorder):
    import time

    before = time.monotonic()
    with recorder.span("clocked") as sp:
        pass
    assert before <= sp.start_mono <= time.monotonic()
    assert abs(sp.start - time.time()) < 60  # the epoch start stays
    assert "start_mono" not in sp.to_dict()  # one process's clock only


def test_stats_honours_the_windows_edges(recorder):
    for end in (10.5, 11.5, 12.5):
        _after_the_fact(recorder, "batch.predict", end - 0.25, 0.25)
    _after_the_fact(recorder, "other", 11.0, 0.5)
    inside = recorder.stats(11.0, 12.0)
    assert inside["batch.predict"] == {
        "count": 1, "total_s": 0.25, "self_s": 0.25}
    assert inside["other"]["count"] == 1
    # a second that overlaps the window counts whole
    assert recorder.stats(10.9, 12.1)["batch.predict"]["count"] == 3
    assert recorder.stats(13.0, 20.0) == {}
    # no upper edge: up to now, far past these hand-made instants
    assert recorder.stats(12.0)["batch.predict"]["count"] == 1


def test_stats_keeps_a_bounded_number_of_seconds(recorder):
    from predictionio_tpu.obs.spans import STATS_WINDOW_S

    for second in range(STATS_WINDOW_S + 50):
        _after_the_fact(recorder, "tick", second + 0.1, 0.2)
    assert len(recorder._stats["tick"]) == STATS_WINDOW_S
    assert recorder.stats(0.0, 49.9) == {}  # the oldest seconds are gone
    assert recorder.stats(0.0, 1e9)["tick"]["count"] == STATS_WINDOW_S


def test_self_time_is_duration_minus_the_union_of_overlapping_children(recorder):
    parent = new_span_id()
    # queue_wait lies inside assemble, as the dispatcher's two spans do
    _after_the_fact(recorder, "batch.queue_wait", 102.0, 3.0, parent=parent)
    _after_the_fact(recorder, "batch.assemble", 101.0, 5.0, parent=parent)
    # a child that sticks out of its parent counts only where it covers it
    _after_the_fact(recorder, "late", 109.0, 4.0, parent=parent)
    _after_the_fact(recorder, "server.request", 100.0, 10.0, span_id=parent)
    row = recorder.stats(100.0, 120.0)["server.request"]
    assert row["total_s"] == pytest.approx(10.0)
    assert row["self_s"] == pytest.approx(10.0 - 5.0 - 1.0)
    assert recorder.stats(100.0, 120.0)["batch.assemble"]["self_s"] == 5.0


def test_self_time_sees_a_child_recorded_from_another_thread(recorder):
    import time

    with recorder.span("query.wait") as parent:
        t0 = time.monotonic()
        time.sleep(0.05)
        covered = time.monotonic() - t0

        def dispatcher():  # after the fact, epoch start only: no start_mono
            recorder.record(Span(
                trace_id=parent.trace_id, span_id=new_span_id(),
                name="batch.device_dispatch", parent_span_id=parent.span_id,
                start=time.time() - covered, duration=covered))

        worker = threading.Thread(target=dispatcher)
        worker.start()
        worker.join(timeout=5)
        assert not worker.is_alive()
    row = recorder.stats(t0 - 1.0)["query.wait"]
    assert row["self_s"] == pytest.approx(parent.duration - covered, abs=0.01)
    assert row["self_s"] < parent.duration - 0.04


def test_collector_gathers_a_jobs_spans_and_what_they_leave_unnamed(recorder):
    import time

    from predictionio_tpu.obs.spans import collect

    with collect() as totals:
        with recorder.span("train") as root:
            with recorder.span("train.read"):
                time.sleep(0.01)
            time.sleep(0.02)  # the root's own: nothing names it
            with recorder.span("train.train") as stage:
                with recorder.span("als.train.degrees") as leaf:
                    time.sleep(0.01)
                with recorder.span("als.train.degrees"):
                    pass
    with recorder.span("outside"):  # after the block: not this job's
        pass
    assert set(totals.seconds) == {
        "train", "train.read", "train.train", "als.train.degrees"}
    assert totals.seconds["train"] == root.duration
    assert totals.seconds["train.train"] == stage.duration
    assert totals.seconds["als.train.degrees"] >= leaf.duration  # summed
    leaves = totals.seconds["train.read"] + totals.seconds["als.train.degrees"]
    assert totals.unattributed == pytest.approx(root.duration - leaves)
    assert totals.unattributed >= 0.02

    # spans of another thread are another job's
    def elsewhere():
        with recorder.span("elsewhere"):
            pass

    with collect() as mine:
        worker = threading.Thread(target=elsewhere)
        worker.start()
        worker.join(timeout=5)
        with recorder.span("here"):
            pass
    assert set(mine.seconds) == {"here"}


def test_detached_span_roots_a_trace_of_its_own(recorder):
    from predictionio_tpu.obs.spans import detached

    with recorder.span("batch.predict") as outer:
        with detached(), recorder.span("batch.serve") as inner:
            pass
        with recorder.span("nested") as nested:
            pass
    assert inner.trace_id != outer.trace_id and inner.parent_span_id is None
    assert nested.parent_span_id == outer.span_id  # the context came back
    assert [s.name for s in recorder.get_trace(inner.trace_id)] == ["batch.serve"]


def test_obs_spans_never_imports_jax():
    import subprocess
    import sys

    code = (
        "import sys\n"
        "from predictionio_tpu.obs import spans\n"
        "import predictionio_tpu.obs\n"
        "with spans.span('x'):\n"
        "    pass\n"
        "assert spans.get_default_recorder().stats(0.0)['x']['count'] == 1\n"
        "print('jax' in sys.modules)\n"
    )
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip() == "False"


def test_span_with_jax_loaded_and_no_profiler_writes_no_trace(
        recorder, tmp_path, monkeypatch):
    import jax

    from predictionio_tpu.obs import spans

    monkeypatch.chdir(tmp_path)
    assert isinstance(spans._trace_annotation("x"), jax.profiler.TraceAnnotation)
    with recorder.span("quiet") as sp:
        pass
    assert sp.duration >= 0.0 and not sp.error
    assert list(tmp_path.iterdir()) == []


def test_span_is_an_event_of_a_running_profiler_trace(recorder, tmp_path):
    """The tentpole: under a profiler trace a program span lands on the
    /host:CPU plane of the same .xplane.pb the device events are in."""
    import glob
    import time

    import jax
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        with recorder.span("als.train.degrees"):
            time.sleep(0.02)
    finally:
        jax.profiler.stop_trace()
    [path] = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    found = [
        ev.duration_ns
        for plane in ProfileData.from_file(path).planes
        if plane.name == "/host:CPU"
        for line in plane.lines for ev in line.events
        if ev.name == "als.train.degrees"
    ]
    assert len(found) == 1 and found[0] >= 15_000_000
