"""Observability: XLA profiler hook, per-stage timings on the
EngineInstance row, remote log shipping (--log-url), and (ISSUE 1) the
unified metrics registry — /metrics exposition on every server, trace-id
propagation, access logs, stats retention."""

import json
import logging
import os
import threading
import time
import urllib.request
from http.server import BaseHTTPRequestHandler, HTTPServer

import numpy as np
import pytest

from predictionio_tpu.core.base import WorkflowParams
from predictionio_tpu.data.event import Event
from predictionio_tpu.data.storage.base import App
from predictionio_tpu.data.storage.registry import (
    SourceConfig,
    Storage,
    StorageConfig,
)
from predictionio_tpu.workflow.core import run_train

VARIANT = {
    "id": "obs",
    "engineFactory":
        "predictionio_tpu.engines.recommendation.RecommendationEngine",
    "datasource": {"params": {"app_name": "obsapp"}},
    "algorithms": [{"name": "als", "params": {"rank": 4, "num_iterations": 2}}],
}


@pytest.fixture()
def storage():
    cfg = StorageConfig(
        sources={"MEM": SourceConfig("MEM", "memory", {})},
        repositories={
            "METADATA": "MEM", "EVENTDATA": "MEM", "MODELDATA": "MEM",
        },
    )
    s = Storage(cfg)
    app_id = s.get_meta_data_apps().insert(App(0, "obsapp"))
    events = s.get_events()
    events.init_app(app_id)
    rng = np.random.RandomState(0)
    events.insert_batch(
        [
            Event(event="rate", entity_type="user",
                  entity_id=f"u{rng.randint(6)}",
                  target_entity_type="item",
                  target_entity_id=f"i{rng.randint(10)}",
                  properties={"rating": float(rng.randint(1, 6))})
            for _ in range(120)
        ],
        app_id,
    )
    return s


def test_stage_timings_recorded_on_instance(storage):
    inst = run_train(storage, VARIANT)
    assert inst.status == "COMPLETED"
    timings = json.loads(inst.env["stage_timings"])
    # the four stages keep their keys; every other span of the job
    # stands beside them under its own name (ISSUE 25)
    assert {"read", "prepare", "train", "persist"} <= set(timings)
    assert all(v >= 0 for v in timings.values())
    # the recorded row round-trips through storage too
    stored = storage.get_meta_data_engine_instances().get(inst.id)
    assert json.loads(stored.env["stage_timings"]) == timings


def test_stage_timings_come_from_every_span_of_the_job(storage):
    """ISSUE 25: the four stage keys keep the values of their stage
    spans; every other span of the job stands beside them by name, with
    the job's root span and what no leaf span covers."""
    from predictionio_tpu.obs.spans import get_default_recorder

    recorder = get_default_recorder()
    inst = run_train(storage, VARIANT)
    timings = json.loads(inst.env["stage_timings"])
    [trace_id] = [sp.trace_id for sp in recorder.recent()
                  if sp.attrs.get("instance_id") == inst.id]
    mine = {}
    for sp in recorder.recent():
        if sp.trace_id == trace_id:
            mine[sp.name] = mine.get(sp.name, 0.0) + sp.duration
    for stage in ("read", "prepare", "train", "persist"):
        assert timings[stage] == round(mine[f"train.{stage}"], 4)
    assert timings["job"] == round(mine["train"], 4)
    for name in ("train.algorithm", "als.train.degrees", "als.stage.host_prep",
                 "als.stage.transfer", "als.train.program",
                 "als.train.copy_back", "persist.serialize", "persist.write"):
        assert timings[name] == pytest.approx(mine[name], abs=1e-4), name
    assert 0.0 <= timings["unattributed"] < timings["job"]
    leaves = sum(v for k, v in timings.items()
                 if k.startswith(("als.", "persist."))
                 or k in ("read", "prepare"))
    assert timings["job"] == pytest.approx(
        leaves + timings["unattributed"], abs=2e-3)


def test_profile_dir_produces_trace(storage, tmp_path):
    profile_dir = str(tmp_path / "xla-trace")
    inst = run_train(
        storage, VARIANT,
        workflow_params=WorkflowParams(profile_dir=profile_dir),
    )
    assert inst.status == "COMPLETED"
    # jax.profiler.trace writes plugins/profile/<ts>/*.{trace.json.gz,xplane.pb}
    produced = []
    for root, _dirs, files in os.walk(profile_dir):
        produced.extend(files)
    assert produced, f"no trace files under {profile_dir}"


class _Collector(BaseHTTPRequestHandler):
    received: list[dict] = []

    def do_POST(self):
        n = int(self.headers.get("Content-Length", 0))
        body = self.rfile.read(n).decode()
        for line in body.splitlines():
            if line.strip():
                type(self).received.append(json.loads(line))
        self.send_response(200)
        self.send_header("Content-Length", "0")
        self.end_headers()

    def log_message(self, *a):
        pass


@pytest.fixture()
def collector():
    _Collector.received = []
    srv = HTTPServer(("127.0.0.1", 0), _Collector)
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    yield f"http://127.0.0.1:{srv.server_address[1]}/logs", _Collector.received
    srv.shutdown()


def test_remote_log_shipping_handler(collector):
    from predictionio_tpu.utils.logship import RemoteLogHandler

    url, received = collector
    logger = logging.getLogger("predictionio_tpu.test.shipper")
    handler = RemoteLogHandler(url, flush_interval=0.1)
    handler.setFormatter(logging.Formatter("%(message)s"))
    logger.addHandler(handler)
    try:
        logger.warning("shipped line %d", 1)
        logger.error("shipped line %d", 2)
        deadline = time.time() + 5
        while len(received) < 2 and time.time() < deadline:
            time.sleep(0.05)
    finally:
        logger.removeHandler(handler)
        handler.close()
    messages = [r["message"] for r in received]
    assert "shipped line 1" in messages and "shipped line 2" in messages
    levels = {r["level"] for r in received}
    assert {"WARNING", "ERROR"} <= levels


def test_query_server_ships_logs(storage, collector):
    """--log-url wiring on the deploy server: server-side log records reach
    the collector (reference CreateServer.scala:441-452)."""
    from predictionio_tpu.workflow.server import (
        QueryServer,
        QueryServerConfig,
        latest_completed_runtime,
    )

    url, received = collector
    run_train(storage, VARIANT)
    runtime = latest_completed_runtime(storage, "obs", "0", "obs")
    srv = QueryServer(
        storage, runtime,
        QueryServerConfig(ip="127.0.0.1", port=0, log_url=url),
    )
    srv.start()
    try:
        # INFO must ship: --log-url promises INFO-level records even when
        # no logging config exists (attach lowers the package logger level)
        logging.getLogger("predictionio_tpu.workflow.server").info(
            "serving log line for the collector"
        )
        deadline = time.time() + 5
        while not received and time.time() < deadline:
            time.sleep(0.05)
    finally:
        srv.stop()
    assert any(
        "serving log line" in r["message"] for r in received
    ), received


# -- unified metrics registry + /metrics + tracing (ISSUE 1) ---------------

def _get(url, headers=None):
    req = urllib.request.Request(url, headers=headers or {})
    with urllib.request.urlopen(req, timeout=15) as r:
        return r.status, dict(r.headers), r.read().decode()


def _post(url, body, headers=None):
    req = urllib.request.Request(
        url, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json", **(headers or {})},
        method="POST",
    )
    with urllib.request.urlopen(req, timeout=15) as r:
        return r.status, dict(r.headers), json.loads(r.read().decode())


def _assert_valid_exposition(text):
    """Every non-comment line must be `name[{labels}] value`, every
    histogram's +Inf bucket must equal its _count."""
    import re

    counts, infs = {}, {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        m = re.fullmatch(
            r"([a-zA-Z_:][a-zA-Z0-9_:]*)(\{.*\})? (\S+)", line
        )
        assert m, f"invalid exposition line: {line!r}"
        name, labels, value = m.groups()
        if name.endswith("_count"):
            counts[(name[:-len("_count")], labels or "")] = float(value)
        if name.endswith("_bucket") and 'le="+Inf"' in (labels or ""):
            key = re.sub(r',?le="\+Inf"', "", labels).replace("{}", "")
            infs[(name[:-len("_bucket")], key or "")] = float(value)
    for key, inf_count in infs.items():
        assert counts.get(key) == inf_count, (key, inf_count, counts)


@pytest.fixture()
def query_served(storage):
    from predictionio_tpu.workflow.server import (
        QueryServer,
        QueryServerConfig,
        latest_completed_runtime,
    )

    run_train(storage, VARIANT)
    runtime = latest_completed_runtime(storage, "obs", "0", "obs")
    srv = QueryServer(
        storage, runtime, QueryServerConfig(ip="127.0.0.1", port=0)
    )
    port = srv.start()
    yield srv, port
    srv.stop()


def test_query_server_metrics_scrape(query_served):
    srv, port = query_served
    status, _h, _b = _post(
        f"http://127.0.0.1:{port}/queries.json", {"user": "u0", "num": 2}
    )
    assert status == 200
    status, headers, text = _get(f"http://127.0.0.1:{port}/metrics")
    assert status == 200
    assert headers["Content-Type"].startswith("text/plain")
    _assert_valid_exposition(text)
    # acceptance: request counter + latency histogram + the query-server
    # specific distributions, all in one scrape
    assert 'http_requests_total{server="query"' in text
    assert "http_request_seconds_bucket" in text
    assert "serve_seconds_bucket" in text
    assert "predict_seconds_bucket" in text
    assert "batch_size_bucket" in text  # micro-batching is on by default
    assert "batch_queue_wait_seconds_bucket" in text
    # JAX runtime gauges sampled on scrape (CPU test backend still counts)
    assert "jax_jit_compile_count" in text
    assert "jax_live_buffer_count" in text
    # train ran in this process → default-registry stages merge into scrape
    assert 'train_stage_seconds_bucket{stage="train"' in text
    # the registry replaced the running averages: properties derive from it
    assert srv.request_count >= 1
    assert srv.avg_serving_sec > 0
    assert srv.metrics.histogram("serve_seconds").quantile(0.5) > 0


def test_trace_id_round_trips_and_hits_access_log(query_served):
    _srv, port = query_served
    records = []

    class _Capture(logging.Handler):
        def emit(self, record):
            records.append(json.loads(record.getMessage()))

    access_logger = logging.getLogger("predictionio_tpu.access")
    handler = _Capture()
    old_level = access_logger.level
    access_logger.addHandler(handler)
    access_logger.setLevel(logging.INFO)
    try:
        status, headers, _b = _post(
            f"http://127.0.0.1:{port}/queries.json", {"user": "u0"},
            headers={"X-Request-ID": "abc"},
        )
        assert status == 200
        assert headers["X-Request-ID"] == "abc"  # client id echoes back
        # no client id → server generates one
        status, headers, _b = _post(
            f"http://127.0.0.1:{port}/queries.json", {"user": "u0"}
        )
        assert len(headers["X-Request-ID"]) == 32
        # ids outside the safe charset are REPLACED, not echoed — the
        # header goes back out in the response, so hostile bytes must
        # never round-trip
        status, headers, _b = _post(
            f"http://127.0.0.1:{port}/queries.json", {"user": "u0"},
            headers={"X-Request-ID": "bad id with spaces"},
        )
        assert headers["X-Request-ID"] != "bad id with spaces"
        assert len(headers["X-Request-ID"]) == 32
    finally:
        access_logger.removeHandler(handler)
        access_logger.setLevel(old_level)
    by_trace = {r["trace_id"]: r for r in records}
    assert "abc" in by_trace, records
    rec = by_trace["abc"]
    assert rec["server"] == "query"
    assert rec["path"] == "/queries.json"
    assert rec["status"] == 200
    assert rec["duration_ms"] > 0


def test_event_server_metrics_scrape(storage):
    from predictionio_tpu.data.api.server import (
        EventServer,
        EventServerConfig,
    )
    from predictionio_tpu.data.storage.base import AccessKey

    app = storage.get_meta_data_apps().get_by_name("obsapp")
    storage.get_meta_data_access_keys().insert(
        AccessKey(key="OBSKEY", app_id=app.id, events=())
    )
    es = EventServer(storage, EventServerConfig(ip="127.0.0.1", port=0))
    port = es.start()
    try:
        status, headers, _b = _post(
            f"http://127.0.0.1:{port}/events.json?accessKey=OBSKEY",
            {"event": "rate", "entityType": "user", "entityId": "u1"},
            headers={"X-Request-ID": "evt-1"},
        )
        assert status == 201
        assert headers["X-Request-ID"] == "evt-1"
        _s, _h, text = _get(f"http://127.0.0.1:{port}/metrics")
        _assert_valid_exposition(text)
        assert 'http_requests_total{server="event"' in text
        assert 'path="/events.json",status="201"' in text
        assert "http_request_seconds_bucket" in text
        assert "events_ingested_total 1" in text
    finally:
        es.stop()


def test_dashboard_and_storage_server_metrics_scrape(storage, tmp_path):
    from predictionio_tpu.data.api.storage_server import StorageServer
    from predictionio_tpu.tools.dashboard import Dashboard

    dash = Dashboard(storage, ip="127.0.0.1", port=0)
    dport = dash.start()
    ss = StorageServer(storage, host="127.0.0.1", port=0).start()
    try:
        _get(f"http://127.0.0.1:{dport}/")  # generate one request
        _s, _h, text = _get(f"http://127.0.0.1:{dport}/metrics")
        _assert_valid_exposition(text)
        assert 'http_requests_total{server="dashboard"' in text

        _get(f"http://127.0.0.1:{ss.port}/health")
        _s, _h, text = _get(f"http://127.0.0.1:{ss.port}/metrics")
        _assert_valid_exposition(text)
        assert 'http_requests_total{server="storage"' in text
    finally:
        ss.shutdown()
        dash.stop()


def test_storage_rpc_counter(storage):
    """RPCs through the remote client land in storage_rpc_total."""
    from predictionio_tpu.data.api.storage_server import StorageServer
    from predictionio_tpu.data.storage.registry import (
        SourceConfig,
        Storage,
        StorageConfig,
    )

    ss = StorageServer(storage, host="127.0.0.1", port=0).start()
    try:
        remote = Storage(StorageConfig(
            sources={"R": SourceConfig(
                "R", "remote", {"HOST": "127.0.0.1", "PORT": str(ss.port)}
            )},
            repositories={
                "METADATA": "R", "EVENTDATA": "R", "MODELDATA": "R",
            },
        ))
        assert remote.get_meta_data_apps().get_by_name("obsapp") is not None
        _s, _h, text = _get(f"http://127.0.0.1:{ss.port}/metrics")
        assert 'storage_rpc_total{dao="apps",method="get_by_name"} 1' in text
    finally:
        ss.shutdown()


def test_stats_retention_cap():
    """Satellite: hourly Stats buckets are pruned past the retention
    horizon instead of leaking forever."""
    import datetime as dt

    from predictionio_tpu.data.api.stats import Stats
    from predictionio_tpu.data.event import Event

    stats = Stats(retention_hours=24)
    ev = Event(event="rate", entity_type="user", entity_id="u1")
    now = dt.datetime.now(dt.timezone.utc)
    for hours_ago in (30, 26, 25):  # beyond retention
        stats.update(1, 201, ev, now=now - dt.timedelta(hours=hours_ago))
    for hours_ago in (23, 1):  # inside retention
        stats.update(1, 201, ev, now=now - dt.timedelta(hours=hours_ago))
    stats.update(1, 201, ev, now=now)  # fresh update triggers the prune
    hours = stats.get(1)["hours"]
    assert len(hours) == 3, hours  # 23h, 1h, now — the stale three pruned
    total = sum(c["count"] for h in hours for c in h["counts"])
    assert total == 3
    # a second app's fresh bucket is untouched by app-1 churn
    stats.update(2, 201, ev, now=now)
    assert len(stats.get(2)["hours"]) == 1


def test_logship_trace_id_and_recovery(collector):
    """Satellite: shipped records carry the active trace id; a recovered
    collector logs its recovery and re-arms the outage warning."""
    from predictionio_tpu.obs.tracing import trace_context
    from predictionio_tpu.utils.logship import RemoteLogHandler

    url, received = collector
    logger = logging.getLogger("predictionio_tpu.test.traceship")
    handler = RemoteLogHandler(url, flush_interval=0.05)
    handler.setFormatter(logging.Formatter("%(message)s"))
    logger.addHandler(handler)
    try:
        with trace_context("trace-xyz"):
            logger.warning("inside the request")
        logger.warning("outside any request")
        deadline = time.time() + 5
        while len(received) < 2 and time.time() < deadline:
            time.sleep(0.05)
    finally:
        logger.removeHandler(handler)
        handler.close()
    by_msg = {r["message"]: r for r in received}
    assert by_msg["inside the request"]["trace_id"] == "trace-xyz"
    assert "trace_id" not in by_msg["outside any request"]

    # recovery: simulate an outage having warned, then ship successfully
    handler2 = RemoteLogHandler(url, flush_interval=3600)
    try:
        handler2._warned = True
        recovery = []

        class _Cap(logging.Handler):
            def emit(self, record):
                recovery.append(record.getMessage())

        ship_logger = logging.getLogger("pio.logship")
        cap = _Cap()
        ship_logger.addHandler(cap)
        ship_logger.setLevel(logging.INFO)
        try:
            assert handler2._ship([{"message": "hello"}])
        finally:
            ship_logger.removeHandler(cap)
        assert handler2._warned is False  # re-armed for the next outage
        assert any("recovered" in m for m in recovery), recovery
    finally:
        handler2.close()


# ---------------------------------------------------------------------------
# ISSUE 3 satellites: /debug/traces filters + jaxmon late-import re-arm
# ---------------------------------------------------------------------------


def test_debug_traces_filters(storage):
    """?min_duration_ms= and ?error=1 pull only slow/errored traces."""
    import uuid

    from predictionio_tpu.obs import spans as _spans
    from predictionio_tpu.tools.admin import AdminServer

    recorder = _spans.get_default_recorder()

    def mk(name, duration, error):
        tid = uuid.uuid4().hex
        recorder.record(
            _spans.Span(
                trace_id=tid, span_id=_spans.new_span_id(), name=name,
                start=time.time(), duration=duration, error=error,
            ),
            finalize=True,
        )
        return tid

    slow_id = mk("t.slow", 0.9, False)     # kept: slow
    err_id = mk("t.err", 0.001, True)      # kept: error
    srv = AdminServer(storage, ip="127.0.0.1", port=0)
    srv.start()
    try:
        def fetch(params):
            with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/debug/traces?{params}",
                timeout=10,
            ) as r:
                return json.loads(r.read().decode())["traces"]

        slow = fetch("min_duration_ms=500")
        assert any(s["trace_id"] == slow_id for s in slow)
        assert all(s["duration_ms"] >= 500 for s in slow)
        errs = fetch("error=1")
        assert any(s["trace_id"] == err_id for s in errs)
        assert all(s["error"] for s in errs)
        both = fetch("error=1&min_duration_ms=500")
        assert all(
            s["error"] and s["duration_ms"] >= 500 for s in both
        )
        assert not any(s["trace_id"] == err_id for s in both)
        # filters respect the limit AFTER filtering
        limited = fetch("min_duration_ms=500&limit=1")
        assert len(limited) <= 1
    finally:
        srv.stop()


def test_jaxmon_rearm_at_scrape_time(monkeypatch):
    """The late-import gap: gauges wired before jax imports must arm the
    compile listener at scrape time, not stay stuck at 0 forever."""
    import sys

    from predictionio_tpu.obs import jaxmon

    calls = []
    monkeypatch.setattr(jaxmon, "_listener_installed", False)
    monkeypatch.setattr(
        jaxmon, "ensure_compile_listener", lambda: calls.append(1)
    )
    # no jax loaded → scrape must NOT trigger the (expensive) import
    monkeypatch.delitem(sys.modules, "jax", raising=False)
    jaxmon._compile_count_now()
    assert calls == []
    # jax has since been imported → the next scrape arms the listener
    sys.modules.setdefault("jax", __import__("types"))
    try:
        jaxmon._compile_count_now()
        jaxmon._compile_seconds_now()
    finally:
        if not hasattr(sys.modules.get("jax"), "__version__"):
            sys.modules.pop("jax", None)
    assert calls == [1, 1]
