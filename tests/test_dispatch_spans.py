"""The dispatcher's hand-offs as spans (ISSUE 37): a query's queue wait split
where the hand-offs happen, the reply's way back, the loop thread's own
states and the stretches with no query held — a stub algorithm behind a real
`_BatchDispatcher` and a real `QueryServer`, on the CPU."""

from __future__ import annotations

import json
import threading
import time
import urllib.request

import pytest

import predictionio_tpu.obs.spans as spans
from predictionio_tpu.workflow import server as S

QUERY_SPANS = ("batch.queue_wait", "batch.pickup", "batch.assemble",
               "batch.slot_wait", "batch.device_dispatch",
               "batch.result_transfer", "query.wake", "query.wait")
STATE_SPANS = ("dispatch.collect", "dispatch.slot", "dispatch.no_work",
               "batch.finish")
CLOSED_BY = {"full", "idle_pipeline", "answers_ready", "window", "wedge"}


class _Owner:
    metrics = None
    tenant_weight = None

    def bookkeep_predict(self, s, n):
        pass

    def count_shed(self, r):
        pass


class _Serving:
    def supplement(self, q):
        return q

    def serve(self, q, preds):
        return preds[0]


class _Algo:
    serving_context = None

    def __init__(self, device_s=0.0):
        self.device_s = device_s

    def batch_predict(self, ctx, model, queries):
        if self.device_s:
            time.sleep(self.device_s)
        return [(i, {"echo": q}) for i, q in queries]

    def predict(self, model, query):
        return {"echo": query}


def _runtime(device_s=0.0):
    return S.EngineRuntime(
        instance=None, engine=None, engine_params=None,
        algorithms=[_Algo(device_s)], models=[None], serving=_Serving(),
        query_class=None,
    )


@pytest.fixture()
def recorder():
    """The process's recorder keeping every trace, with the spans of the
    names under test gathered as they are recorded (a bridge sees a state
    span too, which no store keeps)."""
    rec = spans.get_default_recorder()
    old = (rec.sample_rate, rec.max_traces)
    rec.sample_rate, rec.max_traces = 1.0, 2048
    seen: dict[str, list] = {}
    names = STATE_SPANS + ("batch.predict",)
    for name in names:
        rec.bridge(name, lambda sp, _n=name: seen.setdefault(_n, []).append(sp))
    rec.seen = seen
    yield rec
    for name in names:
        rec.unbridge(name)
    rec.sample_rate, rec.max_traces = old


def _submit_traced(d, rt, query, trace_id):
    """One query as a handler thread submits it: inside a root span of its
    own trace, so the dispatcher's per-query spans have a parent."""
    with spans.span("server.request", trace_id=trace_id, server="query"):
        return d.submit(query, rt, timeout=10)


def _by_name(rec, trace_id):
    out: dict[str, list] = {}
    for sp in rec.get_trace(trace_id):
        out.setdefault(sp.name, []).append(sp)
    return out


def _interval(sp):
    return sp.start_mono, sp.start_mono + sp.duration


def test_queue_wait_is_the_sum_of_its_three_hand_offs(recorder):
    """Forty queries from eight threads over a 10 ms batch: for EVERY query
    pickup + assemble + slot_wait is queue_wait to a microsecond, none is
    negative, and the three lie end to end."""
    d = S._BatchDispatcher(_Owner(), 1.0, 8, 30.0, 2)
    rt = _runtime(device_s=0.01)
    ids = [f"sum-{time.monotonic_ns()}-{i}" for i in range(40)]

    def client(mine):
        for tid in mine:
            _submit_traced(d, rt, tid, tid)

    threads = [threading.Thread(target=client, args=(ids[i::8],))
               for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    d.stop()
    closed = set()
    for tid in ids:
        by = _by_name(recorder, tid)
        for name in QUERY_SPANS:
            assert len(by[name]) == 1, (tid, name)
        whole, pickup, assemble, slot = (
            by[n][0] for n in ("batch.queue_wait", "batch.pickup",
                               "batch.assemble", "batch.slot_wait"))
        parts = pickup.duration + assemble.duration + slot.duration
        assert parts == pytest.approx(whole.duration, abs=1e-6)
        assert min(pickup.duration, assemble.duration, slot.duration) >= 0.0
        assert pickup.start_mono == pytest.approx(whole.start_mono, abs=1e-6)
        assert _interval(assemble)[0] == pytest.approx(
            _interval(pickup)[1], abs=1e-6)
        assert _interval(slot)[0] == pytest.approx(
            _interval(assemble)[1], abs=1e-6)
        root = by["server.request"][0]
        assert {s.parent_span_id for n in QUERY_SPANS for s in by[n]} == {
            root.span_id}
        closed.add(assemble.attrs["closed_by"])
        assert assemble.attrs["batch_size"] >= 1
    assert closed <= CLOSED_BY


def test_closed_by_names_the_branch_that_closed_the_batch(recorder):
    """A lone query on an idle pipeline closes its batch by `idle_pipeline`;
    queries that are all queued before the loop thread gets to them fill the
    batch: `full`. `dispatch.collect` carries the same word and the size."""
    d = S._BatchDispatcher(_Owner(), 1.0, 4, 30.0, 1)
    rt = _runtime(device_s=0.3)
    lone = f"lone-{time.monotonic_ns()}"
    first = threading.Thread(target=_submit_traced, args=(d, rt, lone, lone))
    first.start()
    time.sleep(0.05)  # the lone query's batch is in flight, the loop idle
    # four more while the one slot is taken: the loop thread collects all
    # four (max_batch) before the in-flight batch has its answers
    ids = [f"full-{time.monotonic_ns()}-{i}" for i in range(4)]
    threads = [threading.Thread(target=_submit_traced, args=(d, rt, t, t))
               for t in ids]
    for t in threads:
        t.start()
    for t in [first] + threads:
        t.join()
    d.stop()
    assert _by_name(recorder, lone)["batch.assemble"][0].attrs[
        "closed_by"] == "idle_pipeline"
    for tid in ids:
        assemble = _by_name(recorder, tid)["batch.assemble"][0]
        assert assemble.attrs["closed_by"] == "full"
        assert assemble.attrs["batch_size"] == 4
    collects = recorder.seen["dispatch.collect"]
    assert [(s.attrs["closed_by"], s.attrs["size"]) for s in collects][-2:] == [
        ("idle_pipeline", 1), ("full", 4)]
    # the full batch waited for the lone one's slot: that is its slot_wait,
    # and the loop thread's dispatch.slot
    waits = [_by_name(recorder, t)["batch.slot_wait"][0].duration for t in ids]
    assert min(waits) > 0.03
    assert recorder.seen["dispatch.slot"][-1].duration > 0.03


class _HeldServing(_Serving):
    """Serves a query only once the test lets it."""

    def __init__(self):
        self.gate = threading.Event()

    def serve(self, q, preds):
        assert self.gate.wait(20)
        return preds[0]


class _FirstCallHeld(_Algo):
    """Its first batch_predict says it has begun and returns (or raises
    `fault`) only once the test lets it; later calls return at once."""

    def __init__(self, fault=None):
        super().__init__()
        self.fault = fault
        self.calls: list = []
        self.begun, self.gate = threading.Event(), threading.Event()

    def batch_predict(self, ctx, model, queries):
        self.calls.append(len(queries))
        if len(self.calls) == 1:
            self.begun.set()
            assert self.gate.wait(20)
            if self.fault is not None:
                raise self.fault
        return super().batch_predict(ctx, model, queries)


def _wait_for(cond, what, timeout=10.0):
    deadline = time.monotonic() + timeout
    while not cond():
        assert time.monotonic() < deadline, f"timed out waiting for {what}"
        time.sleep(0.001)


def _behind_a_held_batch(d, rt, algo, ids):
    """One query whose batch_predict is held, then `ids` submitted and
    taken by the loop thread behind it; returns all the threads."""
    lone = f"ahead-{time.monotonic_ns()}"
    threads = [threading.Thread(target=_submit_traced, args=(d, rt, lone, lone))]
    threads[0].start()
    assert algo.begun.wait(10)
    for tid in ids:
        threads.append(threading.Thread(
            target=_submit_traced, args=(d, rt, tid, tid)))
        threads[-1].start()
    _wait_for(lambda: d._queue.qsize() == 0 and d._held == 1 + len(ids),
              "the queries behind to be taken")
    return threads


def test_batch_finish_covers_what_the_next_batch_no_longer_waits_for(recorder):
    """One `batch.finish` a batch, from its `batch.predict`'s end to the
    end of its serve loop, with the batch's size; while the first batch's
    serve is held, the batch that assembled behind it closes by
    `answers_ready` and its `batch.predict` runs INSIDE the first one's
    `batch.finish`."""
    t0 = time.monotonic()
    d = S._BatchDispatcher(_Owner(), 1.0, 8, 30_000.0, 4)
    rt = _runtime()
    rt.algorithms = [algo := _FirstCallHeld()]
    rt.serving = held = _HeldServing()
    ids = [f"behind-{time.monotonic_ns()}-{i}" for i in range(3)]
    threads = _behind_a_held_batch(d, rt, algo, ids)
    algo.gate.set()  # the first batch's answers; its serve is held
    _wait_for(lambda: len(algo.calls) == 2, "the second batch to run")
    assert threads[0].is_alive()  # the first batch is still finishing
    held.gate.set()
    for t in threads:
        t.join(20)
    d.stop()
    # (gathered as they END: the first batch's finish may end last)
    predicts, finishes, collects = (
        sorted((s for s in recorder.seen[n] if s.start_mono >= t0),
               key=lambda s: s.start_mono)
        for n in ("batch.predict", "batch.finish", "dispatch.collect"))
    assert [s.attrs["batch_size"] for s in predicts] == [1, 3]
    assert [s.attrs["batch_size"] for s in finishes] == [1, 3]
    assert [(s.attrs["closed_by"], s.attrs["size"]) for s in collects] == [
        ("idle_pipeline", 1), ("answers_ready", 3)]
    for predict, finish in zip(predicts, finishes):
        assert _interval(finish)[0] >= _interval(predict)[1]
    (f_lo, f_hi), (p_lo, p_hi) = _interval(finishes[0]), _interval(predicts[1])
    assert f_lo < p_lo and p_hi < f_hi
    for tid in ids:
        assemble = _by_name(recorder, tid)["batch.assemble"][0]
        assert assemble.attrs["closed_by"] == "answers_ready"
        # closed at the first batch's answers — not before them, and
        # before its own batch ran, which was before the held serve ended
        assert _interval(predicts[0])[1] - 1e-4 <= _interval(assemble)[1] <= p_lo
    # a state span: accounted, in no trace
    assert recorder.stats(t0)["batch.finish"]["count"] >= 2
    assert all(s.trace_id == spans.NO_TRACE for s in finishes)


def test_a_failed_batch_has_no_finish_and_lets_go_after_its_fallback(recorder):
    """A batch whose batch_predict raised never had its answers: no
    `batch.finish`, and the batch assembling behind it closes only once
    the per-query fallback (programs of its own) is through."""
    t0 = time.monotonic()
    d = S._BatchDispatcher(_Owner(), 1.0, 8, 30_000.0, 4)
    rt = _runtime()
    rt.algorithms = [algo := _FirstCallHeld(fault=RuntimeError("device fault"))]
    in_fallback, fallback = threading.Event(), threading.Event()

    def predict(model, query):
        in_fallback.set()
        assert fallback.wait(20)
        return {"echo": query}

    algo.predict = predict
    threads = _behind_a_held_batch(d, rt, algo, [f"behind-{time.monotonic_ns()}"])
    algo.gate.set()  # the fault strikes; the fallback is held
    assert in_fallback.wait(10)
    time.sleep(0.05)
    assert algo.calls == [1] and len(d._awaiting) == 1
    fallback.set()
    for t in threads:
        t.join(20)
    d.stop()
    assert algo.calls == [1, 1]
    assert len([s for s in recorder.seen["batch.finish"]
                if s.start_mono >= t0]) == 1


def test_query_wake_lies_inside_its_query_wait(recorder):
    d = S._BatchDispatcher(_Owner(), 1.0, 8, 30.0, 2)
    rt = _runtime(device_s=0.005)
    ids = [f"wake-{time.monotonic_ns()}-{i}" for i in range(6)]
    for tid in ids:
        _submit_traced(d, rt, tid, tid)
    d.stop()
    for tid in ids:
        by = _by_name(recorder, tid)
        wake, wait = by["query.wake"][0], by["query.wait"][0]
        assert wake.duration >= 0.0
        (w_lo, w_hi), (q_lo, q_hi) = _interval(wake), _interval(wait)
        assert q_lo - 1e-5 <= w_lo and w_hi <= q_hi + 1e-5
        # it starts where the query's turn in the serve loop ended
        assert w_lo >= _interval(by["batch.result_transfer"][0])[1] - 1e-5


def test_no_work_is_measured_and_never_overlaps_a_batch(recorder):
    """0.3 s without a query is >= 0.25 s of `dispatch.no_work` in stats()
    once the next query ends the stretch; no such interval overlaps a
    `batch.predict` interval; stop() records the last stretch."""
    t0 = time.monotonic()
    d = S._BatchDispatcher(_Owner(), 1.0, 8, 30.0, 2)
    rt = _runtime(device_s=0.02)
    time.sleep(0.3)
    _submit_traced(d, rt, "q", f"idle-{time.monotonic_ns()}")
    stats = recorder.stats(t0)
    assert stats["dispatch.no_work"]["total_s"] >= 0.25
    # a burst (the server is never empty inside it), a pause, one more
    threads = [threading.Thread(
        target=_submit_traced, args=(d, rt, i, f"busy-{time.monotonic_ns()}-{i}"))
        for i in range(12)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    time.sleep(0.25)
    _submit_traced(d, rt, "q", f"idle-{time.monotonic_ns()}")
    before_stop = len(recorder.seen["dispatch.no_work"])
    time.sleep(0.05)
    d.stop()
    idle = [s for s in recorder.seen["dispatch.no_work"]
            if s.start_mono >= t0 - 1e-3]
    assert len(recorder.seen["dispatch.no_work"]) > before_stop
    busy = [_interval(s) for s in recorder.seen["batch.predict"]
            if s.start_mono >= t0]
    assert len(busy) >= 3
    for lo, hi in (_interval(s) for s in idle):
        assert hi > lo
        assert not any(lo < b_hi - 1e-4 and b_lo < hi - 1e-4
                       for b_lo, b_hi in busy)
    # the pieces the idle loop thread flushes are short (a second of the
    # statistics holds what was spent in it) and lie end to end
    assert max(s.duration for s in idle) < 1.0
    whole = time.monotonic() - t0
    assert 0.5 < sum(s.duration for s in idle) < whole
    assert d._held == 0


def test_cancelled_query_is_let_go_of_once(recorder):
    """A query abandoned at its deadline is no longer held, and the entry's
    later shedding does not count it a second time."""
    d = S._BatchDispatcher(_Owner(), 1.0, 8, 30.0, 1)
    rt = _runtime(device_s=0.2)
    blocker = threading.Thread(
        target=lambda: d.submit("slow", rt, timeout=10))
    blocker.start()
    time.sleep(0.05)
    with pytest.raises(S.DeadlineExceeded):
        d.submit("late", rt, timeout=0.02)
    assert d._held == 1  # the blocker's
    blocker.join()
    time.sleep(0.05)
    d.stop()
    assert d._held == 0


@pytest.fixture()
def served(recorder):
    srv = S.QueryServer(
        None, _runtime(device_s=0.003),
        S.QueryServerConfig(ip="127.0.0.1", port=0))
    port = srv.start()
    yield srv, port
    srv.stop()


def _post(port, body, trace_id):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/queries.json", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json", "X-Request-ID": trace_id},
        method="POST")
    with urllib.request.urlopen(req, timeout=30) as r:
        return r.status, json.loads(r.read())


def _get(port, path):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=30) as r:
        return json.loads(r.read())


def test_state_spans_are_in_stats_and_in_no_trace(served, recorder):
    """Through a real QueryServer: every batch has one `dispatch.collect`
    and one `dispatch.slot` in stats(); they and `dispatch.no_work` are in
    `GET /debug/traces?stats=1`, in no retained trace, in no `?spans=1`
    dump, and leave nothing behind in the recorder's `_active`."""
    srv, port = served
    t0 = time.monotonic()
    ids = [f"http-{time.monotonic_ns()}-{i}" for i in range(8)]
    for i, tid in enumerate(ids):
        status, reply = _post(port, {"n": i}, tid)
        assert status == 200 and reply == {"echo": {"n": i}}
        time.sleep(0.03)
    deadline = time.time() + 10
    while time.time() < deadline:
        table = _get(port, "/debug/traces?stats=1&window=30")["spans"]
        if table.get("server.request", {}).get("count", 0) >= 8:
            break
        time.sleep(0.05)
    # (stats() counts to the whole second, so an earlier test's last batch
    # may be in it: the one-of-each is counted on the spans themselves)
    mine = {n: [s for s in recorder.seen[n] if s.start_mono >= t0]
            for n in ("batch.predict", "dispatch.collect", "dispatch.slot")}
    batches = len(mine["batch.predict"])
    assert batches >= 1
    assert len(mine["dispatch.collect"]) == len(mine["dispatch.slot"]) == batches
    stats = recorder.stats(t0)
    for name in ("batch.predict", "dispatch.collect", "dispatch.slot"):
        assert stats[name]["count"] >= batches
    assert stats["dispatch.no_work"]["total_s"] > 0.0
    for name in STATE_SPANS + ("batch.pickup", "batch.assemble",
                               "batch.slot_wait", "query.wake"):
        assert table[name]["count"] >= 1, name
    # the request's trace holds its own spans, and none of the states
    for tid in ids:
        names = {s["name"] for s in _get(
            port, f"/debug/traces?trace_id={tid}")["spans"]}
        assert {"server.request", "query.wait", "query.wake", "batch.pickup",
                "batch.assemble", "batch.slot_wait",
                "batch.queue_wait"} <= names
        assert not names & set(STATE_SPANS)
    listed = _get(port, "/debug/traces?limit=0")["traces"]
    assert not {t["root"] for t in listed} & set(STATE_SPANS)
    dumped = {s["name"] for s in _get(port, "/debug/traces?spans=1")["spans"]}
    assert not dumped & set(STATE_SPANS)
    with recorder._lock:
        assert spans.NO_TRACE not in recorder._active
        assert spans.NO_TRACE not in recorder._traces


def test_closed_batches_are_counted_on_the_servers_registry(served):
    """`dispatch_batches_closed_total{closed_by}` beside `batch_size` on a
    real QueryServer's registry and in its `/metrics`: as many closed as
    ran, each under one of the rule's words."""
    srv, port = served
    for i in range(5):
        assert _post(port, {"n": i}, f"count-{time.monotonic_ns()}")[0] == 200
    closed = srv.metrics.counter(
        "dispatch_batches_closed_total", labelnames=("closed_by",))
    ran = next(f for f in srv.metrics.families() if f.name == "batch_size")
    assert closed.total == ran.count == 5
    assert closed.value(closed_by="idle_pipeline") == 5
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=30) as r:
        text = r.read().decode()
    assert 'dispatch_batches_closed_total{closed_by="idle_pipeline"} 5' in text


def test_state_span_is_accounted_and_never_stored():
    """The recorder's one way to a span no request owns: `state_span()` in
    real time, `record(Span(trace_id=NO_TRACE))` after the fact."""
    rec = spans.SpanRecorder(sample_rate=1.0)
    t0 = time.monotonic()
    with rec.state_span("state.real", size=3) as sp:
        assert spans.current_span_id() is None  # nothing nests under it
        time.sleep(0.01)
    assert sp.attrs == {"size": 3} and sp.duration >= 0.01
    rec.record(spans.Span(
        trace_id=spans.NO_TRACE, span_id=spans.new_span_id(),
        name="state.after", start=time.time() - 0.5, duration=0.5))
    with pytest.raises(RuntimeError):
        with rec.state_span("state.failed"):
            raise RuntimeError("boom")
    stats = rec.stats(t0 - 1.0)
    assert stats["state.real"]["count"] == 1
    assert stats["state.after"]["total_s"] == pytest.approx(0.5)
    assert stats["state.failed"]["count"] == 1
    assert rec.recent() == [] and rec.summaries() == []
    assert not rec._active and not rec._traces
    # a real span beside them is stored as ever
    with rec.span("kept"):
        pass
    assert [s.name for s in rec.recent()] == ["kept"]


def test_record_all_files_like_record_under_one_hold_of_the_lock():
    """A request's after-the-fact children in one call: the bridge, the
    statistics (a parent's self time), the trace — as seven `record()`s."""
    rec = spans.SpanRecorder(sample_rate=1.0)
    bridged = []
    rec.bridge("batch.queue_wait", bridged.append)
    root = spans.new_span_id()
    now = time.time()
    kids = [spans.Span(trace_id="t-all", span_id=spans.new_span_id(),
                       parent_span_id=root, name=name, start=now - 1.0 + 0.1 * i,
                       duration=0.1)
            for i, name in enumerate(("batch.queue_wait", "batch.pickup",
                                      "query.wake"))]
    kids.append(spans.Span(trace_id=spans.NO_TRACE, span_id=spans.new_span_id(),
                           name="dispatch.no_work", start=now - 2.0,
                           duration=0.5))
    rec.record_all(kids)
    assert bridged == [kids[0]]
    assert all(k.start_mono > 0.0 for k in kids)
    rec.record(spans.Span(trace_id="t-all", span_id=root, name="server.request",
                          start=now - 1.0, duration=1.0), finalize=True)
    assert sorted(s.name for s in rec.get_trace("t-all")) == [
        "batch.pickup", "batch.queue_wait", "query.wake", "server.request"]
    stats = rec.stats(time.monotonic() - 5.0)
    assert stats["server.request"]["self_s"] == pytest.approx(0.7, abs=1e-3)
    assert stats["dispatch.no_work"]["total_s"] == pytest.approx(0.5)
    assert not rec._active


def test_deferred_children_wait_for_the_handler_and_are_recorded_now_without():
    """`spans.defer`: behind a handler that opened a deferral the spans wait
    in its list (it records them after the reply's last byte, before the
    root); anywhere else they are recorded at once."""
    rec = spans.get_default_recorder()
    t0 = time.monotonic()

    def kid(name):
        return spans.Span(trace_id=f"t-defer-{t0}", span_id=spans.new_span_id(),
                          name=name, start=time.time() - 0.1, duration=0.1)

    spans.defer([kid("defer.now")])
    assert rec.stats(t0 - 1.0)["defer.now"]["count"] == 1
    pending: list = []
    token = spans.open_deferral(pending)
    try:
        spans.defer([kid("defer.later"), kid("defer.later")])
        assert [s.name for s in pending] == ["defer.later"] * 2
        assert "defer.later" not in rec.stats(t0 - 1.0)
    finally:
        spans.close_deferral(token)
    rec.record_all(pending)
    assert rec.stats(t0 - 1.0)["defer.later"]["count"] == 2
    spans.defer([kid("defer.now")])  # the deferral is closed again
    assert rec.stats(t0 - 1.0)["defer.now"]["count"] == 2


def test_sharded_counters_are_mounted_by_the_first_sharded_batch(served):
    """A server whose engine has no sharded tier shows neither family; the
    first `sharded.dispatch` span mounts both on its registry."""
    srv, _port = served

    def families():
        return {f.name for f in srv.metrics.families()}

    assert not {"sharded_batches_total",
                "sharded_exclusion_bytes_total"} & families()
    with spans.span("sharded.dispatch", batch=2, shards=4, form="rows",
                    exclusion_bytes=64):
        pass
    assert {"sharded_batches_total",
            "sharded_exclusion_bytes_total"} <= families()
    batches = srv.metrics.counter("sharded_batches_total", labelnames=("form",))
    assert batches.value(form="rows") == 1
    assert srv.metrics.counter("sharded_exclusion_bytes_total").total == 64
