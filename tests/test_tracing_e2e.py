"""Cross-process span tracing e2e (ISSUE 2 acceptance): a query through
the query server backed by remote storage yields ONE trace holding the
root server span, the micro-batch queue/device child spans, and the
storage RPC client span parented to the request — with the storage
daemon's own server span parented under the client span via
`X-Parent-Span`. Plus the `X-Request-ID`-on-RPC regression test and the
`pio trace` console commands."""

import json
import threading
import time
import urllib.request
from http.server import BaseHTTPRequestHandler, HTTPServer

import pytest

from predictionio_tpu.data.event import Event
from predictionio_tpu.data.storage.base import App
from predictionio_tpu.data.storage.registry import (
    SourceConfig,
    Storage,
    StorageConfig,
)
from predictionio_tpu.obs.spans import get_default_recorder
from predictionio_tpu.obs.tracing import trace_context


@pytest.fixture()
def keep_all_traces():
    """Tail sampling would probabilistically drop fast, clean test
    traffic — keep everything for the duration of a test."""
    rec = get_default_recorder()
    old = (rec.sample_rate, rec.max_traces)
    rec.sample_rate, rec.max_traces = 1.0, 2048
    yield rec
    rec.sample_rate, rec.max_traces = old


# -- satellite regression: RPCs carry X-Request-ID (+ X-Parent-Span) --------


class _HeaderCapture(BaseHTTPRequestHandler):
    captured: list[dict] = []

    def do_POST(self):
        n = int(self.headers.get("Content-Length", 0))
        self.rfile.read(n)
        type(self).captured.append(dict(self.headers))
        body = json.dumps({"ok": True, "result": None}).encode()
        self.send_response(200)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def log_message(self, *a):
        pass


def test_remote_client_propagates_trace_headers(keep_all_traces):
    """PR-1 gap: `RemoteClient.call` shipped NO `X-Request-ID`, so the
    storage daemon's access logs could not be correlated with the
    calling request. Every RPC must now carry the active trace id and
    the client span's id."""
    from predictionio_tpu.data.storage.remote import RemoteClient

    _HeaderCapture.captured = []
    srv = HTTPServer(("127.0.0.1", 0), _HeaderCapture)
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    try:
        client = RemoteClient({
            "HOST": "127.0.0.1", "PORT": str(srv.server_address[1]),
        })
        with trace_context("rpc-regress-1"):
            client.call("apps", "get_by_name", "whatever")
        # outside any trace: the client span mints a trace id, so the
        # daemon STILL gets a correlatable id on every single RPC
        client.call("apps", "get_all")
    finally:
        srv.shutdown()
    assert len(_HeaderCapture.captured) == 2
    in_trace, bare = _HeaderCapture.captured
    assert in_trace["X-Request-ID"] == "rpc-regress-1"
    assert in_trace.get("X-Parent-Span"), "client span id must propagate"
    assert bare.get("X-Request-ID"), "RPC outside a trace still carries an id"
    # and the client span landed in the recorder under the right trace
    spans = keep_all_traces.get_trace("rpc-regress-1")
    rpc = [s for s in spans if s.name == "storage.rpc"]
    assert rpc and rpc[0].attrs["dao"] == "apps"
    assert in_trace["X-Parent-Span"] == rpc[0].span_id


# -- acceptance e2e ---------------------------------------------------------


UR_VARIANT = {
    "id": "trace-ur",
    "engineFactory":
        "predictionio_tpu.engines.universal.UniversalRecommenderEngine",
    "datasource": {
        "params": {"app_name": "traceapp", "indicators": ["buy"]}
    },
    "algorithms": [
        {
            "name": "ur",
            "params": {"app_name": "traceapp", "indicators": ["buy"]},
        }
    ],
}


def _get_json(url):
    with urllib.request.urlopen(url, timeout=15) as r:
        return r.status, json.loads(r.read().decode())


def test_trace_spans_cross_process_query(keep_all_traces):
    """The acceptance path: query server + storage daemon (remote
    EVENTDATA, so the UR history fetch RPCs at serve time), one traced
    query, one merged span tree, valid Perfetto export."""
    from predictionio_tpu.data.api.storage_server import StorageServer
    from predictionio_tpu.workflow.core import run_train
    from predictionio_tpu.workflow.server import (
        QueryServer,
        QueryServerConfig,
        latest_completed_runtime,
    )

    backing = Storage(StorageConfig(
        sources={"MEM": SourceConfig("MEM", "memory", {})},
        repositories={
            "METADATA": "MEM", "EVENTDATA": "MEM", "MODELDATA": "MEM",
        },
    ))
    daemon = StorageServer(backing, host="127.0.0.1", port=0).start()
    srv = None
    try:
        remote = Storage(StorageConfig(
            sources={"R": SourceConfig(
                "R", "remote",
                {"HOST": "127.0.0.1", "PORT": str(daemon.port)},
            )},
            repositories={
                "METADATA": "R", "EVENTDATA": "R", "MODELDATA": "R",
            },
        ))
        app_id = remote.get_meta_data_apps().insert(App(0, "traceapp"))
        remote.get_events().init_app(app_id)
        # two cohorts over 8 items so cross-occurrence has signal
        events = [
            Event(event="buy", entity_type="user", entity_id=f"u{u}",
                  target_entity_type="item",
                  target_entity_id=f"i{(u % 2) * 4 + j}")
            for u in range(12) for j in range(4)
        ]
        remote.get_events().insert_batch(events, app_id)

        inst = run_train(remote, UR_VARIANT)
        assert inst.status == "COMPLETED"
        runtime = latest_completed_runtime(
            remote, "trace-ur", "0", "trace-ur"
        )
        srv = QueryServer(
            remote, runtime, QueryServerConfig(ip="127.0.0.1", port=0)
        )
        port = srv.start()

        trace_id = "e2e-trace-accept"
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/queries.json",
            data=json.dumps(
                {"user": "u0", "num": 4, "exclude_seen": True}
            ).encode(),
            headers={
                "Content-Type": "application/json",
                "X-Request-ID": trace_id,
            },
            method="POST",
        )
        with urllib.request.urlopen(req, timeout=30) as r:
            assert r.status == 200
            assert r.headers["X-Request-ID"] == trace_id

        # the root span records just after the response bytes go out —
        # poll /debug/traces (which also exercises the endpoint)
        spans = None
        deadline = time.time() + 10
        while time.time() < deadline:
            status, data = _get_json(
                f"http://127.0.0.1:{port}/debug/traces?trace_id={trace_id}"
            )
            if status == 200:
                spans = data["spans"]
                break
            time.sleep(0.05)
        assert spans, "trace never appeared on /debug/traces"

        by_name: dict = {}
        for s in spans:
            by_name.setdefault(s["name"], []).append(s)
        # ONE trace: root server span of the query server...
        roots = [
            s for s in by_name["server.request"]
            if s["attrs"]["server"] == "query"
        ]
        assert len(roots) == 1
        root = roots[0]
        assert root["parent_span_id"] is None
        assert root["attrs"]["path"] == "/queries.json"
        # ...micro-batch queue + device child spans under the root...
        queue = by_name["batch.queue_wait"][0]
        device = by_name["batch.device_dispatch"][0]
        assert queue["parent_span_id"] == root["span_id"]
        assert device["parent_span_id"] == root["span_id"]
        assert "batch.assemble" in by_name
        assert "batch.result_transfer" in by_name
        # ...the storage RPC client span parented to the request (under
        # the device span the history fetch ran in)...
        rpcs = by_name["storage.rpc"]
        fetch = [s for s in rpcs if s["attrs"]["dao"] == "events"]
        assert fetch, rpcs
        # (since ISSUE 25 through the batch's own batch.predict span, and
        # since ISSUE 35 through the engine's own: the history read is a
        # span of its own inside the batch's prepare phase)
        predict = by_name["batch.predict"][0]
        assert predict["parent_span_id"] == device["span_id"]
        prepare = by_name["ur.predict.prepare"][0]
        assert prepare["parent_span_id"] == predict["span_id"]
        read = by_name["ur.history_read"][0]
        assert read["parent_span_id"] == prepare["span_id"]
        assert read["attrs"]["events"] == 4  # u0's four buys
        assert all(s["parent_span_id"] == read["span_id"] for s in fetch)
        for name in ("ur.predict.device", "ur.predict.decode"):
            assert by_name[name][0]["parent_span_id"] == predict["span_id"]
        # the request's own phases stand beside the dispatcher's spans
        for name in ("query.decode", "query.wait", "query.encode"):
            assert by_name[name][0]["parent_span_id"] == root["span_id"]
        # ...and the storage DAEMON's server span parented under the rpc
        # client span across the process boundary via X-Parent-Span
        daemon_spans = [
            s for s in by_name["server.request"]
            if s["attrs"]["server"] == "storage"
        ]
        assert daemon_spans
        client_ids = {s["span_id"] for s in rpcs}
        assert all(
            s["parent_span_id"] in client_ids for s in daemon_spans
        )

        # Perfetto export of that trace validates as Chrome trace JSON
        export = keep_all_traces.perfetto_export(trace_id)
        parsed = json.loads(json.dumps(export))
        xs = [e for e in parsed["traceEvents"] if e["ph"] == "X"]
        assert {e["args"]["trace_id"] for e in xs} == {trace_id}
        assert len(xs) == len(spans)
        assert all(e["ph"] in ("X", "M") for e in parsed["traceEvents"])
        assert all(
            isinstance(e["ts"], (int, float))
            and isinstance(e["dur"], (int, float))
            for e in xs
        )
        procs = {
            e["args"]["name"]
            for e in parsed["traceEvents"] if e["ph"] == "M"
        }
        assert "query" in procs and "storage" in procs
        # the endpoint serves the same export
        status, remote_export = _get_json(
            f"http://127.0.0.1:{port}/debug/traces"
            f"?trace_id={trace_id}&format=perfetto"
        )
        assert status == 200
        assert len(remote_export["traceEvents"]) == len(
            parsed["traceEvents"]
        )
        # and format=perfetto WITHOUT a trace_id exports all retained
        # traces (what `pio trace export --url` with no id requests)
        status, all_export = _get_json(
            f"http://127.0.0.1:{port}/debug/traces?format=perfetto"
        )
        assert status == 200
        assert len(all_export["traceEvents"]) >= len(parsed["traceEvents"])

        # the summary listing shows it
        _s, listing = _get_json(
            f"http://127.0.0.1:{port}/debug/traces?limit=2048"
        )
        assert any(
            t["trace_id"] == trace_id for t in listing["traces"]
        )
        assert listing["sampling"]["sample_rate"] == 1.0

        # keep-alive reuse: a SECOND query on the same persistent
        # connection (same handler thread) must get a fresh, fully
        # parented trace — no span context may leak from the first
        import http.client as _hc

        conn = _hc.HTTPConnection("127.0.0.1", port, timeout=30)
        try:
            for tid2 in ("ka-trace-1", "ka-trace-2"):
                conn.request(
                    "POST", "/queries.json",
                    body=json.dumps({"user": "u1", "num": 2}).encode(),
                    headers={
                        "Content-Type": "application/json",
                        "X-Request-ID": tid2,
                    },
                )
                resp = conn.getresponse()
                assert resp.status == 200
                resp.read()
        finally:
            conn.close()
        deadline = time.time() + 10
        while time.time() < deadline:
            spans2 = keep_all_traces.get_trace("ka-trace-2")
            if spans2:
                break
            time.sleep(0.05)
        assert spans2
        assert all(s.trace_id == "ka-trace-2" for s in spans2)
        ids1 = {s.span_id for s in keep_all_traces.get_trace("ka-trace-1")}
        roots2 = [
            s for s in spans2
            if s.name == "server.request" and s.attrs["server"] == "query"
        ]
        assert len(roots2) == 1 and roots2[0].parent_span_id is None
        # every child parents within ITS trace, never into the previous
        # request's spans
        for s in spans2:
            assert s.parent_span_id not in ids1

        # the TRAIN trace exists too: stages as spans, RPC children
        train_traces = [
            t for t in listing["traces"] if t["root"] == "train"
        ]
        assert train_traces
        train_spans = keep_all_traces.get_trace(
            train_traces[0]["trace_id"]
        )
        names = {s.name for s in train_spans}
        assert {"train", "train.read", "train.train",
                "train.algorithm", "train.persist"} <= names
        # the read stage's storage RPCs hang off the train trace
        assert any(s.name == "storage.rpc" for s in train_spans)
    finally:
        if srv is not None:
            srv.stop()
        daemon.shutdown()


def test_pio_trace_console(keep_all_traces, tmp_path, capsys):
    from predictionio_tpu.tools.console import main

    with trace_context("cli-trace-1"):
        with keep_all_traces.span("server.request", server="query",
                                  path="/queries.json"):
            with keep_all_traces.span("batch.device_dispatch"):
                pass

    assert main(["trace", "list", "--limit", "2048"]) == 0
    out = capsys.readouterr().out
    assert "cli-trace-1" in out

    assert main(["trace", "show", "cli-trace-1"]) == 0
    out = capsys.readouterr().out
    assert "server.request" in out
    assert "batch.device_dispatch" in out

    dest = tmp_path / "trace.json"
    assert main(["trace", "export", "cli-trace-1",
                 "--output", str(dest)]) == 0
    exported = json.loads(dest.read_text())
    assert any(
        e["ph"] == "X" and e["args"]["trace_id"] == "cli-trace-1"
        for e in exported["traceEvents"]
    )

    assert main(["trace", "show", "no-such-trace"]) == 1
