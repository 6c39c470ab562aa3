"""Shared threaded-HTTP plumbing for the framework's server processes.

Both the Event Server (data/api/server.py) and the deploy query server
(workflow/server.py) are stdlib ThreadingHTTPServer processes with the
same needs: JSON responses, eager body drain (an unread POST body desyncs
HTTP/1.1 keep-alive — the next request parses it as a request line),
routed logging, and a start/stop/port lifecycle."""

from __future__ import annotations

import json
import logging
import re
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Optional

import predictionio_tpu.obs.registry as _obs_registry
import predictionio_tpu.obs.spans as _obs_spans
import predictionio_tpu.obs.tracing as _obs_tracing
import predictionio_tpu.resilience.deadline as _deadline

log = logging.getLogger(__name__)


class HttpError(Exception):
    """Raise inside a handler to produce a JSON error response."""

    def __init__(self, status: int, message: str):
        super().__init__(message)
        self.status = status
        self.message = message


class JsonHandler(BaseHTTPRequestHandler):
    """Base handler: drains the body before dispatch, JSON helpers, and
    the observability middleware — every request is timed, tagged with a
    trace id (`X-Request-ID` from the client or generated here), counted
    into the owning server's MetricsRegistry
    (`http_requests_total{server,method,path,status}` +
    `http_request_seconds{server,path}`), and access-logged as one JSON
    record. Servers opt in by setting `metrics` (a MetricsRegistry) and
    `metrics_label` on their ThreadedServer; trace ids propagate
    regardless."""

    protocol_version = "HTTP/1.1"
    # status line / headers / body are separate socket writes: with
    # Nagle on, the later writes wait for the peer's delayed ACK — a
    # flat ~40 ms stall per response (measured on the storage RPC path;
    # applies equally to event-server and query-server replies)
    disable_nagle_algorithm = True

    def log_message(self, fmt, *args):  # route through logging, not stderr
        log.debug("%s " + fmt, self.address_string(), *args)

    def handle_one_request(self):
        self._raw_body = b""
        self._trace_token = None
        self._span_token = None
        self._deadline_token = None
        self._root_span = None
        # child spans built after the fact on this thread (the
        # dispatcher's, a query) wait here for the reply to be out
        self._deferred_spans: list = []
        deferral = _obs_spans.open_deferral(self._deferred_spans)
        try:
            super().handle_one_request()
        finally:
            _obs_spans.close_deferral(deferral)
            self._record_spans()  # whatever a reply never sent left behind
            # keep-alive reuses this thread: clear the request's trace id,
            # span context and deadline so the next request (or idle
            # logging) can't inherit them
            if self._deadline_token is not None:
                _deadline.reset(self._deadline_token)
                self._deadline_token = None
            if self._span_token is not None:
                _obs_spans.reset_current_span(self._span_token)
                self._span_token = None
            if self._trace_token is not None:
                _obs_tracing.reset_trace_id(self._trace_token)
                self._trace_token = None

    # client-supplied ids are echoed into RESPONSE headers: restrict to a
    # safe charset/length (a folded header would otherwise smuggle CRLF
    # bytes through http.client's parser into the response — header
    # injection / keep-alive desync)
    _TRACE_ID_RE = re.compile(r"[A-Za-z0-9._:-]{1,128}")

    def parse_request(self):
        ok = super().parse_request()
        if ok:
            self._t0 = time.perf_counter()
            self._start_wall = time.time()
            self._metrics_recorded = False
            tid = self.headers.get("X-Request-ID") or ""
            if not self._TRACE_ID_RE.fullmatch(tid):
                tid = _obs_tracing.new_request_id()
            self._trace_id = tid
            self._trace_token = _obs_tracing.set_trace_id(tid)
            # span context: X-Parent-Span carries the CALLER's span id
            # across the process boundary, so this request's root server
            # span parents under the remote client span (same id charset
            # rules as the trace id — both echo into downstream headers)
            psp = self.headers.get("X-Parent-Span") or ""
            self._parent_span = psp if self._TRACE_ID_RE.fullmatch(psp) else None
            self._span_id = _obs_spans.new_span_id()
            self._span_token = _obs_spans.set_current_span(self._span_id)
            # deadline propagation (ISSUE 4): X-PIO-Deadline carries the
            # caller's REMAINING budget in ms; it becomes this request's
            # ambient deadline so handlers can shed expired work and
            # downstream RPC clients shrink their retry budgets to fit
            dl = _deadline.parse_header(self.headers.get(_deadline.HEADER))
            if dl is not None:
                self._deadline_token = _deadline.set_deadline(dl)
        return ok

    # -- observability middleware ------------------------------------------
    def _route_label(self, path: str) -> str:
        """Collapse per-entity path segments so metric label cardinality
        stays bounded (/events/<id>.json → /events/{id}.json; admin's
        /cmd/app/<name>[/data] → /cmd/app/{name}[/data])."""
        parts = path.split("/")
        if len(parts) >= 3 and parts[1] in ("jobs", "models", "tenants"):
            # lifecycle + tenancy control planes: job/version/tenant ids
            # are unbounded (and /tenants/{id}/queries.json is the
            # serving hot path — one tenant, one label child)
            parts[2] = "{id}"
        elif len(parts) >= 3 and parts[1] in ("events", "engine_instances"):
            for suffix in (".json", ".html"):
                if parts[2].endswith(suffix):
                    parts[2] = "{id}" + suffix
                    break
            else:
                parts[2] = "{id}"
        elif (
            len(parts) >= 4
            and parts[1] == "cmd"
            and parts[2] in ("app", "channel", "accesskey")
        ):
            # per-entity admin routes: the name/id segment is
            # client-chosen — every distinct app would otherwise mint a
            # metric child per delete/show
            parts[3] = "{name}"
        return "/".join(parts)

    def _record_request(self, status: int) -> None:
        if getattr(self, "_metrics_recorded", True):
            return
        self._metrics_recorded = True
        duration = time.perf_counter() - self._t0
        label = getattr(self.server, "metrics_label", "http")
        real_path = self.path.split("?")[0].rstrip("/") or "/"
        route = self._route_label(real_path)
        # unmatched routes share ONE metric label value: an internet-facing
        # port gets scanned with unbounded distinct paths, and each would
        # otherwise mint a fresh counter+histogram child. The access log
        # and the span keep the real path — logs and the bounded trace
        # store have no cardinality constraint, and per-entity debugging
        # needs to see WHICH entity the request touched.
        metric_path = "(unmatched)" if status == 404 else route
        registry = getattr(self.server, "metrics", None)
        if registry is not None:
            registry.counter(
                "http_requests_total",
                "HTTP requests served",
                # label-bound: path through _route_label's table
                # (cardinality-guard test), method/status from HTTP
                ("server", "method", "path", "status"),
            ).inc(
                server=label, method=self.command,
                path=metric_path, status=status,
            )
            registry.histogram(
                "http_request_seconds",
                "request wall time, request line to response written",
                ("server", "path"),  # label-bound: _route_label table
            ).observe(duration, server=label, path=metric_path)
        _obs_tracing.log_access(
            server=label,
            method=self.command,
            path=real_path,
            status=status,
            duration_s=duration,
            trace_id=getattr(self, "_trace_id", None),
        )
        # root server span: parents under the caller's span when the
        # request came with X-Parent-Span (cross-process), else starts
        # the trace. finalize=True runs the tail-sampling decision over
        # every span this request's handling recorded.
        attrs = {
            "server": label,
            "method": self.command,
            "path": real_path,
            "status": status,
        }
        if route != real_path:
            attrs["route"] = route  # the metric label this request fed
        # identity attrs the owning process declared (ISSUE 16): a
        # replica sets {"replica": id} here so its server spans stay
        # attributable after the collector stitches them into a fleet
        # trace alongside other replicas' identically-named spans
        extra = getattr(self.server, "span_attrs", None)
        if extra:
            attrs.update(extra)
        # built here, where the request's duration is taken; recorded by
        # `_record_spans` once the body is written
        self._root_span = _obs_spans.Span(
            trace_id=self._trace_id,
            span_id=self._span_id,
            parent_span_id=getattr(self, "_parent_span", None),
            name="server.request",
            start=getattr(self, "_start_wall", time.time()),
            duration=duration,
            attrs=attrs,
            error=status >= 500,
        )

    def _record_spans(self) -> None:
        """The request's deferred child spans, then its root span, which
        finalizes the trace — after the reply's last byte (ISSUE 37): the
        recorder's lock and a dozen dict operations are work on this
        thread that the client need not wait for."""
        recorder = _obs_spans.get_default_recorder()
        pending = getattr(self, "_deferred_spans", None)
        if pending:
            recorder.record_all(pending)
            del pending[:]
        root, self._root_span = getattr(self, "_root_span", None), None
        if root is not None:
            recorder.record(root, finalize=True)

    def _serve_metrics(self) -> None:
        """GET /metrics: this server's registry merged with the
        process-default one (train-stage metrics live there)."""
        text = _obs_registry.render_merged(
            getattr(self.server, "metrics", None),
            _obs_registry.get_default_registry(),
        )
        self._respond(200, text, "text/plain; version=0.0.4")

    def _serve_debug_traces(self) -> None:
        """GET /debug/traces — recent retained traces (tail-sampled);
        `?stats=1[&window=<seconds>]` for where the last seconds went by
        span name (count, total and self seconds of EVERY span, before
        sampling); `?trace_id=` for one trace's full span list, plus
        `&format=perfetto` for Chrome trace-event JSON of it;
        `?min_duration_ms=` / `?error=1` filter the summary listing so
        operators pull only slow/errored traces without exporting the
        whole store. Every JsonHandler server mounts this, same as
        /metrics."""
        from urllib.parse import parse_qsl, urlsplit

        qs = dict(parse_qsl(urlsplit(self.path).query))
        recorder = _obs_spans.get_default_recorder()
        if qs.get("spans") in ("1", "true", "yes"):
            # raw recent-span dump (pre-sampling) for the fleet trace
            # collector: `?spans=1[&since=<epoch-s>]`
            try:
                since = float(qs.get("since", 0) or 0)
            except ValueError:
                since = 0.0
            self._respond(200, {
                "now": time.time(),
                "spans": [s.to_dict() for s in recorder.recent(since)],
            })
            return
        if qs.get("stats") in ("1", "true", "yes"):
            try:
                window = float(qs.get("window", 60) or 60)
            except ValueError:
                window = 60.0
            window = min(max(window, 1.0), float(_obs_spans.STATS_WINDOW_S))
            self._respond(200, {
                "window_s": window,
                "spans": recorder.stats(time.monotonic() - window),
            })
            return
        if qs.get("fleet") in ("1", "true", "yes"):
            self._serve_fleet_traces(qs)
            return
        capture_id = qs.get("capture")
        if capture_id:
            cap = recorder.capture_status(capture_id)
            if cap is None:
                self._respond(
                    404, {"message": f"no capture {capture_id}"}
                )
                return
            self._respond(200, cap)
            return
        trace_id = qs.get("trace_id")
        if qs.get("format") == "perfetto":
            # with trace_id: that one trace; without: every retained one
            export = recorder.perfetto_export(trace_id)
            if trace_id and not export["traceEvents"]:
                self._respond(404, {"message": f"no trace {trace_id}"})
                return
            self._respond(200, export)
            return
        if trace_id:
            spans = recorder.get_trace(trace_id)
            if not spans:
                self._respond(404, {"message": f"no trace {trace_id}"})
                return
            self._respond(200, {
                "trace_id": trace_id,
                "spans": [s.to_dict() for s in spans],
            })
            return
        try:
            limit = int(qs.get("limit", "50"))
        except ValueError:
            limit = 50
        try:
            min_ms = float(qs.get("min_duration_ms", 0) or 0)
        except ValueError:
            min_ms = 0.0
        error_only = qs.get("error") in ("1", "true", "yes")
        if min_ms > 0 or error_only:
            # filter over the FULL store, then apply the limit — the
            # newest N unfiltered rows would hide older slow/errored
            # traces, which are exactly what the filters exist to find
            summaries = [
                s for s in recorder.summaries(limit=0)
                if s["duration_ms"] >= min_ms
                and (not error_only or s["error"])
            ]
            if limit:
                summaries = summaries[:limit]
        else:
            summaries = recorder.summaries(limit=limit)
        self._respond(200, {
            "traces": summaries,
            "sampling": recorder.config(),
        })

    def _serve_fleet_traces(self, qs: dict) -> None:
        """`GET /debug/traces?fleet=1` — the ASSEMBLED cross-process
        traces from this process's fleet trace collector (ISSUE 16):
        summaries by default, `&trace_id=` for one stitched tree,
        `&format=perfetto` for the Chrome trace-event export. 503 on
        processes that don't run a collector (replicas, bare servers)."""
        from predictionio_tpu.obs.monitor import get_monitor

        collector = get_monitor().collector
        if collector is None:
            self._respond(503, {
                "message": "no fleet trace collector runs in this "
                           "process (gateways, dashboards and `pio "
                           "monitor` own one)",
            })
            return
        trace_id = qs.get("trace_id")
        if qs.get("format") == "perfetto":
            export = collector.perfetto_export(trace_id)
            if trace_id and not export["traceEvents"]:
                self._respond(404, {"message": f"no trace {trace_id}"})
                return
            self._respond(200, export)
            return
        if trace_id:
            spans = collector.get_trace(trace_id)
            if not spans:
                self._respond(404, {"message": f"no trace {trace_id}"})
                return
            self._respond(200, {"trace_id": trace_id, "spans": spans})
            return
        try:
            limit = int(qs.get("limit", "50"))
        except ValueError:
            limit = 50
        self._respond(200, {
            "traces": collector.summaries(limit=limit),
            "collector": collector.status(),
        })

    def _serve_debug_tsdb(self) -> None:
        """GET /debug/tsdb — the in-process time-series history (ISSUE
        8): no params lists series; `?name=` returns points, with
        optional `labels=k:v,...`, `window_s=`, and
        `agg=rate|increase|quantile&q=`. Every JsonHandler server
        mounts this next to /metrics."""
        from urllib.parse import parse_qsl, urlsplit

        from predictionio_tpu.obs.monitor import get_monitor

        qs = dict(parse_qsl(urlsplit(self.path).query))
        self._respond(200, get_monitor().tsdb_payload(qs))

    def _serve_alerts(self) -> None:
        """GET /alerts — the SLO engine's alert states (ISSUE 8):
        pending/firing/resolved per declared SLO, with live burn
        rates. Mounted on the query, admin, and dashboard servers."""
        from predictionio_tpu.obs.monitor import get_monitor

        self._respond(200, get_monitor().alerts_payload())

    def _serve_traces_capture(self) -> None:
        """POST /debug/traces/capture {"n": N} — arm the span recorder
        so the dispatcher force-samples the next N batches' traces
        regardless of PIO_TRACE_SAMPLE (ISSUE 8 satellite, the PR-3
        follow-up). Returns a capture id for
        `GET /debug/traces?capture=<id>`. The query server routes this
        — it owns the dispatcher that consumes the arm."""
        body = self._json_body()
        n = 1
        if isinstance(body, dict) and "n" in body:
            try:
                n = int(body["n"])
            except (TypeError, ValueError):
                raise HttpError(400, "'n' must be an integer")
        if not 1 <= n <= 64:
            raise HttpError(400, "'n' must be in [1, 64]")
        capture_id = _obs_spans.get_default_recorder().arm_capture(n)
        self._respond(200, {"capture": capture_id, "batches": n})

    def _serve_debug_profile(self) -> None:
        """GET /debug/profile — the device-profiling report: per-
        executable XLA cost/memory analysis, derived MFU / HBM roofline
        numbers, and padding-waste accounting. Empty-but-valid on
        processes that never loaded jax."""
        from predictionio_tpu.obs import devprof as _devprof

        self._respond(200, _devprof.report())

    def _serve_profile_capture(self) -> None:
        """POST /debug/profile/capture — on-demand jax.profiler trace
        window. Guarded: disabled (403) unless the operator set
        PIO_PROFILE_CAPTURE_DIR on the server process; 409 when jax is
        not loaded here or a capture is already running. Body:
        {"seconds": 2.0} (bounded to (0, 60])."""
        import time as _time

        from predictionio_tpu.obs import devprof as _devprof
        from predictionio_tpu.utils.env import env_path as _env_path

        cap_dir = _env_path("PIO_PROFILE_CAPTURE_DIR")
        if not cap_dir:
            self._respond(403, {
                "message": "profiler capture is disabled: set "
                           "PIO_PROFILE_CAPTURE_DIR on this server to "
                           "enable it"
            })
            return
        body = self._json_body()
        seconds = 2.0
        if isinstance(body, dict) and "seconds" in body:
            try:
                seconds = float(body["seconds"])
            except (TypeError, ValueError):
                raise HttpError(400, "'seconds' must be a number")
        out_dir = _os.path.join(
            cap_dir, _time.strftime("capture-%Y%m%d-%H%M%S")
        )
        try:
            result = _devprof.capture_trace(out_dir, seconds)
        except ValueError as e:
            raise HttpError(400, str(e))
        except RuntimeError as e:
            raise HttpError(409, str(e))
        self._respond(200, result)

    def _serve_debug_faults(self) -> None:
        """GET /debug/faults — the process's active fault specs. Every
        JsonHandler server mounts this next to /metrics (read-only, so
        ungated; mutation goes through the gated POST below)."""
        from predictionio_tpu.resilience import faults as _faults

        self._respond(200, {"faults": _faults.specs()})

    def _serve_debug_faults_set(self) -> None:
        """POST /debug/faults — install/clear fault specs at runtime.
        Guarded like /debug/profile/capture: 403 unless the operator set
        PIO_FAULTS_ADMIN=1 on the server process. Body:
        {"set": "point:mode:prob[:param][,...]", "seed": N} and/or
        {"clear": "point" | true}."""
        from predictionio_tpu.resilience import faults as _faults
        from predictionio_tpu.utils.env import env_flag as _env_flag

        if not _env_flag("PIO_FAULTS_ADMIN"):
            self._respond(403, {
                "message": "fault-injection admin is disabled: set "
                           "PIO_FAULTS_ADMIN=1 on this server to enable it"
            })
            return
        body = self._json_body()
        if not isinstance(body, dict):
            raise HttpError(400, "fault admin body must be a JSON object")
        # validate the whole request BEFORE mutating anything: a
        # malformed `set` must 400 without having executed the `clear`
        spec_text = body.get("set")
        specs = []
        if spec_text:
            seed = body.get("seed")
            try:
                specs = _faults.parse_specs(
                    spec_text, int(seed) if seed is not None else None
                )
            except (_faults.FaultSpecError, TypeError, ValueError) as e:
                raise HttpError(400, str(e))
        clear = body.get("clear")
        if clear is True:
            _faults.clear()
        elif isinstance(clear, str):
            _faults.clear(clear)
        for spec in specs:
            _faults.install(spec)
        self._respond(200, {"faults": _faults.specs()})

    def _serve_telemetry_push(self) -> None:
        """POST /telemetry/push — ingest a pushed telemetry payload from
        an ephemeral process (ISSUE 17). Guarded like /debug/faults: 403
        unless the operator set PIO_PUSH_INGEST=1 on this server, so an
        internet-facing query server can't be fed fabricated series.
        Body is the :mod:`obs.monitor.push` payload (v1: series + spans
        + optional devprof report); lands in the process monitor's TSDB
        tagged ``instance``/``job_id`` and in its trace collector."""
        from predictionio_tpu.obs.monitor import push as _push
        from predictionio_tpu.utils.env import env_flag as _env_flag

        if not _env_flag("PIO_PUSH_INGEST"):
            self._respond(403, {
                "message": "telemetry push ingest is disabled: set "
                           "PIO_PUSH_INGEST=1 on this server to enable it"
            })
            return
        body = self._json_body()
        try:
            result = _push.ingest(
                body, token=self.headers.get(_push.TOKEN_HEADER)
            )
        except _push.PushAuthError as e:
            raise HttpError(403, str(e))
        except _push.PushError as e:
            raise HttpError(400, str(e))
        self._respond(200, result)

    def _drain_body(self) -> None:
        length = int(self.headers.get("Content-Length") or 0)
        self._raw_body = self.rfile.read(length) if length else b""

    def _body(self) -> bytes:
        return self._raw_body

    def _json_body(self) -> Any:
        try:
            return json.loads(self._body().decode() or "null")
        except json.JSONDecodeError as e:
            raise HttpError(400, f"invalid JSON: {e}")

    def _respond(
        self, status: int, body: Any, content_type: str = "application/json",
        headers: Optional[dict] = None,
    ) -> None:
        data = (
            body.encode() if isinstance(body, str) else json.dumps(body).encode()
        )
        self.send_response(status)
        self.send_header("Content-Type", f"{content_type}; charset=UTF-8")
        self.send_header("Content-Length", str(len(data)))
        trace_id = getattr(self, "_trace_id", None)
        if trace_id:
            self.send_header("X-Request-ID", trace_id)
        for k, v in (headers or {}).items():
            self.send_header(k, str(v))
        self.end_headers()
        # account BEFORE the body write: the moment the client sees the
        # last byte it may issue a follow-up scrape, and the counter for
        # THIS request must already be visible to it (recording after
        # the write loses that race — observed as a missing
        # http_requests_total child on single-vCPU hosts). The final
        # body-write syscall falls outside the measured duration;
        # headers are already on the wire by this point. The request's
        # SPANS go into the recorder after the write: a reader of
        # /debug/traces polls for a trace, as it always had to
        self._record_request(status)
        try:
            self.wfile.write(data)
        finally:
            self._record_spans()


class ThreadedServer(ThreadingHTTPServer):
    daemon_threads = True
    allow_reuse_address = True
    # socketserver's default listen backlog of 5 drops connections under
    # concurrent load (micro-batched serving expects bursts of clients)
    request_queue_size = 128


class ServerProcess:
    """start/stop/port lifecycle shared by server processes. Subclasses
    implement `_make_server() -> ThreadedServer` and set `_name`."""

    _name = "http-server"

    def __init__(self):
        self._server: Optional[ThreadedServer] = None
        self._thread: Optional[threading.Thread] = None
        self._monitor_token: Optional[int] = None

    def _make_server(self) -> ThreadedServer:
        raise NotImplementedError

    @property
    def port(self) -> int:
        assert self._server is not None, "server not started"
        return self._server.server_address[1]

    def start(self) -> int:
        self._server = self._make_server()
        self._thread = threading.Thread(
            target=self._server.serve_forever, name=self._name, daemon=True
        )
        self._thread.start()
        # monitoring plane (ISSUE 8): register this server's registry
        # with the process monitor — the TSDB sampler starts with the
        # first attached server and joins when the last one stops
        registry = getattr(self._server, "metrics", None)
        if registry is not None and self._monitor_token is None:
            from predictionio_tpu.obs.monitor import get_monitor

            self._monitor_token = get_monitor().attach(
                getattr(self._server, "metrics_label", self._name),
                registry,
            )
        # remote log shipping (reference CreateServer.scala:441-452
        # --log-url): any server whose config carries log_url ships the
        # framework's log records to the collector
        log_url = getattr(getattr(self, "config", None), "log_url", None)
        if log_url and getattr(self, "_log_shipper", None) is None:
            import logging

            from predictionio_tpu.utils.logship import attach_log_shipper

            self._log_shipper = attach_log_shipper(
                log_url, logging.getLogger("predictionio_tpu")
            )
        return self.port

    def stop(self) -> None:
        if self._monitor_token is not None:
            from predictionio_tpu.obs.monitor import get_monitor

            get_monitor().detach(self._monitor_token)
            self._monitor_token = None
        shipper = getattr(self, "_log_shipper", None)
        if shipper is not None:
            import logging

            logging.getLogger("predictionio_tpu").removeHandler(shipper)
            shipper.close()
            self._log_shipper = None
        if self._server is not None:
            self._server.shutdown()
            self._server.server_close()
            self._server = None

    def wait(self) -> None:
        """Block until the serving thread exits — stop() from another
        thread, or a server that stops itself (`GET /stop`)."""
        thread = self._thread
        if thread is not None:
            thread.join()

    def serve_forever(self) -> None:
        self.start()
        self.wait()
