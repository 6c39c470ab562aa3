"""Deploy server: REST query serving from TPU-resident model state.

Reference: core/.../workflow/CreateServer.scala:80-713 — MasterActor
(bind/stop/reload orchestration :277), ServerActor spray route :402:
POST /queries.json (:490) does extract → supplement → per-algo predictBase
→ serve → JSON (:499-525), feedback loop (:534-596), plugin chain
(:598-601), request bookkeeping (:603-610), HTML status page (:461-489),
/reload hot-swap (:337-358), /stop.

Re-design: the actor system becomes a threaded HTTP server sharing an
atomically-swapped `EngineRuntime` reference — queries in flight keep the
old runtime during /reload (the MasterActor hot-swap semantic), and model
arrays stay device-resident across queries."""

from __future__ import annotations

import dataclasses
import datetime as _dt
import html as _html
import json
import logging
import threading
import time
import urllib.request
from dataclasses import dataclass, field
from typing import Any, Optional

import predictionio_tpu.obs.spans as _spans
import predictionio_tpu.obs.tracing as _tracing
import predictionio_tpu.resilience.deadline as _deadline
import predictionio_tpu.resilience.faults as _faults
from predictionio_tpu.controller.params import ParamsError, extract_params
from predictionio_tpu.resilience.deadline import DeadlineExceeded
from predictionio_tpu.obs import BATCH_SIZE_BUCKETS, server_registry
from predictionio_tpu.obs.jaxmon import compile_snapshot
from predictionio_tpu.core.base import RuntimeContext
from predictionio_tpu.data.storage.base import EngineInstance, StorageError
from predictionio_tpu.data.storage.registry import Storage
from predictionio_tpu.utils.http import (
    HttpError as _HttpError,
    JsonHandler,
    ServerProcess,
    ThreadedServer,
)
from predictionio_tpu.workflow.core import prepare_deploy_models

log = logging.getLogger(__name__)
from predictionio_tpu.analysis import tsan as _tsan

OUTPUT_BLOCKER = "outputblocker"
OUTPUT_SNIFFER = "outputsniffer"


@dataclass
class QueryServerConfig:
    ip: str = "0.0.0.0"
    port: int = 8000
    # feedback loop (reference CreateServer.scala:534-596)
    feedback: bool = False
    event_server_url: Optional[str] = None  # e.g. http://127.0.0.1:7070
    access_key: Optional[str] = None
    plugins: list = field(default_factory=list)
    # micro-batching: coalesce concurrent queries into one device program
    # (the "one model, many queries → batched inference queue" hard part,
    # SURVEY.md §7 — no reference analogue; JVM serving was per-request).
    # ON by default — the measured fast path IS the default path.
    # `batch_window_ms` / `max_window_ms` are WINDOWED mode's timers (its
    # window adapts between the two: it grows when drains saturate
    # max_batch and decays back when traffic is light). Continuous mode
    # (the default) closes a batch on events and waits on no window:
    # `max_window_ms` is left to it only as the scale of `wedge`, the
    # backstop for a batch whose answers never come (10 × max_window_ms,
    # or 1.2 × the last batch if that is longer).
    micro_batch: bool = True
    batch_window_ms: float = 2.0
    max_window_ms: float = 60.0
    max_batch: int = 64
    # batches on the worker pool at once (VERDICT r3 #3). Short of a
    # backlog that fills whole batches (`full` hands over at once, and
    # XLA queues the programs on the device stream), at most ONE of them
    # is between hand-over and its answers (its batch_predict has not
    # returned: nothing runs concurrently inside a ShardedRuntime or the
    # resident correlators); the others are FINISHING — serving their
    # queries, setting their futures — each on its own slot beside the
    # next batch's put, launch and program (span `batch.finish`). A
    # closed batch that finds every slot taken waits for one
    # (`batch.slot_wait`). 1 lets a batch start only when the one ahead
    # has let go of the pool.
    pipeline_depth: int = 4
    # continuous batching (ISSUE 11; the close rule is ISSUE 38's): a
    # batch closes at once when the queue is dry and no batch awaits its
    # answers (`closed_by` idle_pipeline: a lone query on an idle
    # pipeline waits for nothing); while one does, arrivals keep joining
    # the ASSEMBLING batch, which closes the instant that batch's
    # batch_predict returns (answers_ready) — not when a window expires,
    # not when the worker retires. The loop thread sleeps on the one
    # condition an arrival and a batch's answers both notify.
    # "windowed" restores the PR-2 adaptive-window behavior with its own
    # timers (ROADMAP D4: nothing measures the two against each other
    # any more).
    batching: str = "continuous"
    # adaptive continuous-batching admission (ISSUE 14 satellite,
    # carried serving-kernel follow-up): while a bucket ASSEMBLES in
    # continuous mode with more than one tenant stream active, each
    # tenant may claim at most `admission_cap` slots of it (0 = auto:
    # max_batch // active streams, floor 1) — a hog's backlog cannot
    # fill the whole assembling bucket ahead of other tenants'
    # still-arriving queries; its overflow simply waits for the next
    # bucket. Untenanted traffic counts as one stream.
    admission_cap: int = 0
    # tenant-aware drain (ISSUE 11 satellite, carried tenancy
    # follow-up): with tenants active, stop lingering for full depth as
    # soon as every still-backlogged tenant is represented in the
    # assembling bucket — fairness needs one group per tenant per
    # round, not a full bucket. Windowed mode only (continuous mode's
    # answers-ready signal supersedes it); kept a separate knob so it is
    # testable in isolation.
    tenant_drain: bool = True
    # remote log shipping (reference CreateServer.scala:441-452 --log-url):
    # server log records POST to this collector as JSON lines, best-effort
    log_url: Optional[str] = None


@dataclass
class EngineRuntime:
    """Everything needed to answer queries; swapped atomically on /reload."""

    instance: EngineInstance
    engine: Any
    engine_params: Any
    algorithms: list[Any]
    models: list[Any]
    serving: Any
    query_class: Optional[type]
    query_serializer: Optional[Any] = None
    started_at: _dt.datetime = field(
        default_factory=lambda: _dt.datetime.now(_dt.timezone.utc)
    )


def build_runtime(storage: Storage, instance: EngineInstance) -> EngineRuntime:
    """Re-hydrate a COMPLETED instance into a servable runtime (reference
    createServerActorWithEngine, CreateServer.scala:206)."""
    from predictionio_tpu.obs.jaxmon import ensure_compile_listener

    # fault point (ISSUE 4): a failed model load/rehydration must leave
    # the PREVIOUS runtime serving (reload() swaps only on success) —
    # chaos tests inject here to prove the query server keeps answering
    # from the last-loaded model when storage/model data is unreachable
    _faults.fire("model.load")

    # hook BEFORE rehydration/warmup: those jit-compile, and the compile
    # gauges must count them even though no server exists yet
    ensure_compile_listener()
    engine, engine_params, models = prepare_deploy_models(storage, instance)
    algorithms = engine.make_algorithms(engine_params)
    serving = engine.make_serving(engine_params)
    serving_ctx = RuntimeContext(storage=storage, mode="serve")
    for algo, model in zip(algorithms, models):
        algo.set_serving_context(serving_ctx)
        warmup = getattr(algo, "warmup", None)
        if callable(warmup):
            # a failed warm-up RAISES: it runs the very programs the
            # first queries will, so a server that came up anyway would
            # report itself live and then 500. On /reload the previous
            # runtime keeps serving — the swap happens only on success.
            warmup(model)
    query_class = algorithms[0].query_class() if algorithms else None
    query_serializer = (
        algorithms[0].query_serializer() if algorithms else None
    )
    return EngineRuntime(
        instance=instance,
        engine=engine,
        engine_params=engine_params,
        algorithms=algorithms,
        models=models,
        serving=serving,
        query_class=query_class,
        query_serializer=query_serializer,
    )


def latest_completed_runtime(
    storage: Storage, engine_id: str, engine_version: str, variant_id: str
) -> EngineRuntime:
    instance = storage.get_meta_data_engine_instances().get_latest_completed(
        engine_id, engine_version, variant_id
    )
    if instance is None:
        raise RuntimeError(
            f"no COMPLETED engine instance for {engine_id}/{engine_version}/"
            f"{variant_id} — run train first"
        )
    return build_runtime(storage, instance)


def _to_jsonable(obj: Any) -> Any:
    if dataclasses.is_dataclass(obj) and not isinstance(obj, type):
        return {k: _to_jsonable(v) for k, v in dataclasses.asdict(obj).items()}
    if isinstance(obj, dict):
        return {k: _to_jsonable(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_to_jsonable(v) for v in obj]
    if hasattr(obj, "item") and not isinstance(obj, (str, bytes)):
        try:
            return obj.item()  # numpy scalar → python
        except Exception:
            pass
    return obj


class _Handler(JsonHandler):
    server: "_Server"  # type: ignore[assignment]

    def do_GET(self):
        self._drain_body()
        path = self.path.split("?")[0].rstrip("/") or "/"
        try:
            if path == "/":
                self._respond(200, self.server.owner.status_html(), "text/html")
            elif path == "/rollout/status":
                self._respond(200, self.server.owner.rollout_status())
            elif path == "/online/status":
                self._respond(200, self.server.owner.online_status())
            elif path == "/fleet/status":
                self._respond(200, self.server.owner.fleet_serving_status())
            elif path == "/health":
                # cheap liveness for the gateway's active probes (the
                # status page renders HTML and walks the runtime; a
                # probe must cost neither)
                self._respond(200, {"status": "alive"})
            elif path == "/replica/status":
                self._respond(200, self.server.owner.replica_status())
            elif path == "/tenants" or path.startswith("/tenants/"):
                self._tenants_get(path)
            elif path == "/metrics":
                self._serve_metrics()
            elif path == "/alerts":
                self._serve_alerts()
            elif path == "/debug/traces":
                self._serve_debug_traces()
            elif path == "/debug/tsdb":
                self._serve_debug_tsdb()
            elif path == "/debug/profile":
                self._serve_debug_profile()
            elif path == "/debug/faults":
                self._serve_debug_faults()
            elif path == "/reload":
                try:
                    self.server.owner.reload()
                except RolloutConflict as e:
                    self._respond(409, {"message": str(e)})
                else:
                    self._respond(200, {"message": "Reload successful"})
            elif path == "/stop":
                self._respond(200, {"message": "Shutting down"})
                # lint: disable=thread-lifecycle — self-stop: the server
                # cannot join the thread that tears it down (stop() joins
                # THIS handler's pool); the thread exits with the process
                threading.Thread(
                    target=self.server.owner.stop,
                    name="server-self-stop", daemon=True,
                ).start()
            else:
                self._respond(404, {"message": "Not Found"})
        except Exception as e:
            log.exception("GET %s failed", path)
            self._respond(500, {"message": str(e)})

    def do_POST(self):
        self._drain_body()
        path = self.path.split("?")[0].rstrip("/")
        if path == "/queries.json":
            # tenant-tagged queries also ride the plain route via the
            # X-PIO-Tenant header (the path form is canonical); an
            # EMPTY header value means untenanted, not tenant ""
            self._queries(
                tenant_id=self.headers.get("X-PIO-Tenant") or None
            )
        elif path.startswith("/tenants/"):
            self._tenants_post(path)
        elif path == "/reload":
            try:
                self.server.owner.reload()
                self._respond(200, {"message": "Reload successful"})
            except RolloutConflict as e:
                self._respond(409, {"message": str(e)})
            except Exception as e:
                log.exception("reload failed")
                self._respond(500, {"message": str(e)})
        elif path == "/replica/drain":
            # graceful drain (ISSUE 15): the gateway (or an operator)
            # retires this replica — flag the registry record so
            # routing stops, finish in-flight queries, then stop
            owner = self.server.owner
            if owner.replica is None:
                self._respond(
                    404, {"message": "not a replica (no member attached)"}
                )
            elif owner.replica.drain():
                self._respond(202, owner.replica_status())
            else:
                self._respond(409, {"message": "already draining"})
        elif path == "/replica/prefetch":
            # scale-up warm-start (ISSUE 15): the gateway tells a
            # JOINING replica which tenants will hash onto it, so the
            # first real query is a cache hit instead of a model load
            owner = self.server.owner
            body = self._json_body()
            tenants = (
                body.get("tenants") if isinstance(body, dict) else None
            ) or []
            if not isinstance(tenants, list):
                self._respond(400, {"message": "'tenants' must be a list"})
            else:
                accepted = owner.prefetch_tenants(
                    [str(t) for t in tenants]
                )
                self._respond(200, {"accepted": accepted})
        elif path in ("/online/pause", "/online/resume"):
            owner = self.server.owner
            if owner.online is None:
                self._respond(
                    404, {"message": "no online consumer attached"}
                )
            elif path == "/online/pause":
                body = self._json_body()
                reason = (
                    body.get("reason") if isinstance(body, dict) else None
                ) or "operator pause"
                owner.online.pause(reason)
                self._respond(200, owner.online_status())
            else:
                owner.online.resume()
                self._respond(200, owner.online_status())
        elif path in ("/rollout/start", "/rollout/abort"):
            try:
                body = self._json_body()
                if not isinstance(body, dict):
                    body = {}
                if path == "/rollout/start":
                    self._respond(
                        200, self.server.owner.start_rollout(body)
                    )
                else:
                    self._respond(
                        200, self.server.owner.abort_rollout(
                            body.get("reason") or "operator abort"
                        )
                    )
            except _HttpError as e:
                self._respond(e.status, {"message": e.message})
            except ValueError as e:
                self._respond(400, {"message": str(e)})
            except RolloutConflict as e:
                self._respond(409, {"message": str(e)})
            except Exception as e:
                log.exception("rollout request failed")
                self._respond(500, {"message": str(e)})
        elif path == "/debug/traces/capture":
            # arm "trace the next N batches" (ISSUE 8 satellite): only
            # meaningful where a dispatcher exists to consume the arm
            try:
                if self.server.owner.dispatcher is None:
                    self._respond(409, {
                        "message": "micro-batching is disabled: no "
                                   "dispatcher to capture batches from"
                    })
                else:
                    self._serve_traces_capture()
            except _HttpError as e:
                self._respond(e.status, {"message": e.message})
        elif path == "/debug/profile/capture":
            try:
                self._serve_profile_capture()
            except _HttpError as e:
                self._respond(e.status, {"message": e.message})
            except Exception as e:
                log.exception("profiler capture failed")
                self._respond(500, {"message": str(e)})
        elif path == "/debug/faults":
            try:
                self._serve_debug_faults_set()
            except _HttpError as e:
                self._respond(e.status, {"message": e.message})
        else:
            self._respond(404, {"message": "Not Found"})

    # -- multi-tenant control surface (ISSUE 6) ----------------------------
    def _tenants_get(self, path: str) -> None:
        from predictionio_tpu.tenancy import UnknownTenant

        mux = self.server.owner.tenancy
        if mux is None:
            self._respond(
                404, {"message": "multi-tenant serving is not enabled"}
            )
            return
        parts = [p for p in path.split("/") if p]
        try:
            if len(parts) == 1:
                self._respond(200, mux.status())
            elif len(parts) == 2:
                self._respond(200, mux.tenant_status(parts[1]))
            elif len(parts) in (3, 4) and parts[2] == "rollout" and (
                len(parts) == 3 or parts[3] == "status"
            ):
                self._respond(200, mux.rollout_status(parts[1]))
            else:
                self._respond(404, {"message": "Not Found"})
        except UnknownTenant:
            self._respond(404, {"message": f"no tenant {parts[1]!r}"})

    def _tenants_post(self, path: str) -> None:
        from predictionio_tpu.tenancy import UnknownTenant

        owner = self.server.owner
        mux = owner.tenancy
        parts = [p for p in path.split("/") if p]
        if mux is None:
            self._respond(
                404, {"message": "multi-tenant serving is not enabled"}
            )
            return
        if len(parts) == 3 and parts[2] == "queries.json":
            self._queries(tenant_id=parts[1])
            return
        if len(parts) == 4 and parts[2] == "rollout" and parts[3] in (
            "start", "abort"
        ):
            try:
                body = self._json_body()
                if not isinstance(body, dict):
                    body = {}
                if parts[3] == "start":
                    self._respond(200, mux.start_rollout(parts[1], body))
                else:
                    self._respond(200, mux.abort_rollout(
                        parts[1], body.get("reason") or "operator abort"
                    ))
            except _HttpError as e:
                self._respond(e.status, {"message": e.message})
            except UnknownTenant:
                self._respond(404, {"message": f"no tenant {parts[1]!r}"})
            except RolloutConflict as e:
                self._respond(409, {"message": str(e)})
            except ValueError as e:
                self._respond(400, {"message": str(e)})
            except Exception as e:
                log.exception("tenant rollout request failed")
                self._respond(500, {"message": str(e)})
            return
        self._respond(404, {"message": "Not Found"})

    def _queries(self, tenant_id: Optional[str] = None):
        """In-flight accounting wrapper: graceful drain (ISSUE 15)
        waits for this count to reach zero before the replica stops,
        so a retiring replica answers everything it admitted."""
        owner = self.server.owner
        owner.inflight_enter()
        try:
            self._queries_inner(tenant_id)
        finally:
            owner.inflight_exit()

    def _queries_inner(self, tenant_id: Optional[str] = None):
        """The serving hot path (reference CreateServer.scala:490-613)."""
        owner = self.server.owner
        t0 = time.perf_counter()
        # sticky routing bucket (ISSUE 15): a gateway fronting this
        # replica computes crc32(body) % 10000 ONCE and forwards it, so
        # every replica (and every hedged retry) makes the same canary
        # decision; absent the header, the replica hashes locally
        bucket: Optional[int] = None
        rh = self.headers.get("X-PIO-Route-Hash")
        if rh:
            try:
                bucket = int(rh) % 10_000
            except ValueError:
                bucket = None
        # load shedding (ISSUE 4): a query whose propagated deadline
        # (X-PIO-Deadline, set as the ambient deadline by JsonHandler)
        # already passed is refused BEFORE parsing, batching, or device
        # time — the client stopped waiting, any work is pure waste
        if _deadline.expired():
            owner.count_shed("deadline")
            self._respond(
                503,
                {"message": "deadline expired; request shed"},
                headers={"Retry-After": "1"},
            )
            return
        # tenant admission (ISSUE 6): resolve the tenant and enforce its
        # quotas BEFORE parse/batch/device time — an over-quota request
        # is the tenant's doing and gets 429 + Retry-After, deliberately
        # distinct from the deadline/overload 503 above
        mux = owner.tenancy
        tenant = None
        lease = None
        dl_token = None  # tenant deadline-floor clamp (reset in finally)
        if tenant_id is not None:
            from predictionio_tpu.tenancy import (
                QuotaExceeded,
                UnknownTenant,
            )

            if mux is None:
                self._respond(
                    404,
                    {"message": "multi-tenant serving is not enabled"},
                )
                return
            try:
                tenant = mux.admit(tenant_id)
            except UnknownTenant:
                self._respond(
                    404, {"message": f"no tenant {tenant_id!r}"}
                )
                return
            except QuotaExceeded as e:
                owner.count_shed("quota")
                self._respond(
                    429,
                    {"message": str(e)},
                    headers={
                        "Retry-After": str(
                            max(1, int(e.retry_after_s + 0.999))
                        )
                    },
                )
                return
            # per-tenant X-PIO-Deadline floor (ISSUE 10 satellite):
            # clamp the ambient deadline AT ADMIT — a request with no
            # deadline (or a longer one) gets the tenant's budget, so
            # this tenant's slow clients can't hold dispatcher leases
            # past it (the dispatcher submit + shed paths below read
            # the ambient deadline)
            floor_ms = getattr(tenant, "deadline_floor_ms", None)
            if floor_ms:
                cap = time.monotonic() + floor_ms / 1000.0
                cur = _deadline.current()
                if cur is None or cur > cap:
                    dl_token = _deadline.set_deadline(cap)
        variant: Optional[str] = None  # set once routing lands
        variant_booked = False

        def _book(seconds: float, error: bool) -> None:
            if tenant is not None:
                mux.bookkeep(tenant_id, variant, seconds, error)
            else:
                owner.bookkeep_variant(variant, seconds, error)

        try:
            # the request's phases are spans (ISSUE 25): body -> Query and
            # routing here, query.wait in dispatcher.submit, result -> JSON
            with _spans.span("query.decode", server="query"):
                raw = self._raw_body.decode()
                try:
                    query_json = json.loads(raw or "null")
                except json.JSONDecodeError as e:
                    raise _HttpError(400, f"invalid query JSON: {e}")
                # canary routing (ISSUE 5): sticky hash-of-request fraction
                # goes to the candidate runtime; snapshot semantics match
                # /reload — the query is extracted and served against ONE
                # runtime even if a swap lands mid-flight. Tenant queries
                # (ISSUE 6) route through the model cache instead — a miss
                # is a transparent model load, and the returned lease keeps
                # the runtime un-evictable until bookkeeping finishes.
                if tenant is not None:
                    from predictionio_tpu.tenancy import ModelLoadError

                    try:
                        rt, variant, lease = mux.route(
                            tenant, self._raw_body, bucket=bucket
                        )
                    except ModelLoadError as e:
                        raise _HttpError(503, str(e))
                else:
                    rt, variant = owner.pick_runtime(
                        self._raw_body, bucket=bucket
                    )
                custom_from = getattr(
                    rt.query_serializer, "query_from_json", None
                )
                if custom_from is None and not isinstance(query_json, dict):
                    raise _HttpError(400, "query must be a JSON object")
                try:
                    if custom_from is not None:
                        query = custom_from(query_json)
                    elif rt.query_class is not None:
                        query = extract_params(rt.query_class, query_json)
                    else:
                        query = query_json
                except ParamsError as e:
                    raise _HttpError(400, str(e))
                except ValueError as e:
                    raise _HttpError(400, f"query serializer rejected: {e}")

                supplemented = rt.serving.supplement(query)
            try:
                if owner.dispatcher is not None:
                    prediction = owner.dispatcher.submit(
                        supplemented, rt, deadline=_deadline.current(),
                        tenant=tenant_id if tenant is not None else None,
                    )
                else:
                    tp = time.perf_counter()
                    predictions = [
                        algo.predict(model, supplemented)
                        for algo, model in zip(rt.algorithms, rt.models)
                    ]
                    dt_predict = time.perf_counter() - tp
                    owner.bookkeep_predict(dt_predict, 1)
                    if tenant is not None:
                        # no dispatcher → no batch-level charge site:
                        # debit the measured inline predict time here so
                        # the device-seconds quota enforces either way
                        owner.charge_device_seconds(tenant_id, dt_predict)
                    prediction = rt.serving.serve(supplemented, predictions)
            except ValueError as e:
                # algorithms raise ValueError for query-level contract
                # violations (e.g. category filter without category data)
                raise _HttpError(400, str(e))
            with _spans.span("query.encode", server="query"):
                custom_to = getattr(rt.query_serializer, "result_to_json", None)
                result = (
                    custom_to(prediction) if custom_to is not None
                    else _to_jsonable(prediction)
                )
                # shadow agreement compares the SERIALIZED result before
                # output blockers run — blockers may stamp per-request data
                # (ids, timestamps) that would read as disagreement
                shadow_reference = result

                for plugin in owner.output_blockers:
                    result = plugin.process(query_json, result, {})

                owner.bookkeep(time.perf_counter() - t0)
                _book(time.perf_counter() - t0, error=False)
                variant_booked = True
                if tenant is None:
                    # server-level shadow mirroring and the feedback loop
                    # are single-tenant surfaces; tenant traffic must not
                    # leak into the server rollout's agreement windows
                    owner.maybe_shadow(
                        self._raw_body, query_json, shadow_reference,
                        bucket=bucket,
                    )
                    owner.feedback_async(query_json, result)
                for plugin in owner.output_sniffers:
                    try:
                        plugin.process(query_json, result, {})
                    except Exception:
                        log.exception("output sniffer failed")
                # encoded inside the span; _respond then only writes, and
                # records server.request once its children are all in
                payload = json.dumps(result)
            self._respond(200, payload)
        except _HttpError as e:
            # post-routing 4xx DO feed the verdict windows: a candidate
            # whose stricter query class 400s its whole traffic
            # fraction — while live serves the same bodies 200 — shows
            # up as a candidate-only error delta and triggers the
            # rollback it deserves (without this it never reaches
            # min_requests and fails its fraction forever). PRE-routing
            # failures (undecodable body, malformed JSON) stay out of
            # BOTH windows — they never reached either variant, and
            # booking them to one side would skew the delta.
            if variant is not None:
                _book(time.perf_counter() - t0, error=True)
            self._respond(e.status, {"message": e.message})
        except DeadlineExceeded as e:
            # expired in the queue or dispatch outran its budget: the
            # honest answer is "retry later", not a 500 (the dispatcher's
            # drain loop counts the shed, so no double counting here).
            # Sheds feed the windows too: global overload sheds both
            # variants proportionally (delta ≈ 0), but a pathologically
            # slow candidate shedding only ITS fraction must be judged.
            if variant is not None:
                _book(time.perf_counter() - t0, error=True)
            self._respond(
                503, {"message": str(e)}, headers={"Retry-After": "1"}
            )
        except Exception as e:
            log.exception("query failed")
            if variant is not None and not variant_booked:
                # a failure AFTER the success bookkeeping (broken pipe
                # writing the 200) must not record the same request a
                # second time as an error — the canary verdict would
                # see inflated candidate error rates on client hangups
                _book(time.perf_counter() - t0, error=True)
            self._respond(500, {"message": str(e)})
        finally:
            if dl_token is not None:
                _deadline.reset(dl_token)
            if tenant is not None:
                # release the cache lease (the runtime becomes evictable
                # again) and the tenant's concurrency slot
                mux.done(tenant_id, lease)


class _Pending:
    """One queued query awaiting a device batch. `deadline` is an
    absolute time.monotonic() bound (None = unbounded); `cancelled` is
    set by the submitting handler when its client stopped waiting, so
    the drain loop skips the entry instead of burning a device dispatch
    on an answer nobody will read (ISSUE 4 satellite: the old tuple
    entries had no way to be withdrawn). `tenant` (ISSUE 6) tags the
    entry for the fair scheduler's per-tenant sub-queue and for the
    dispatcher's device-seconds accounting.

    The entry also carries its own timeline (ISSUE 37), one
    `time.perf_counter()` stamp a hand-off, written by the thread that
    makes the hand-off and read by the handler thread once the future
    is done (`_BatchDispatcher._record_query_spans`): `t_submit` (the
    handler puts it), `t_taken` (the loop thread takes it), `t_closed`
    and `closed_by` (its batch closes), `t_run` (`_run_group` starts on
    a pool thread), `t_dispatched` (batch_predict is back), `t_serve` /
    `t_served` (its turn in the serve loop), `t_resolved` (its future
    is set). A stamp still None names a hand-off that never happened.
    `held` is the dispatcher's count of it (`_hold` / `_release`)."""

    __slots__ = (
        "query", "runtime", "fut", "t_submit", "tctx", "deadline",
        "cancelled", "tenant", "held", "t_taken", "t_closed", "closed_by",
        "t_run", "batch_size", "dev_span_id", "t_dispatched",
        "dispatch_error", "t_serve", "t_served", "serve_error",
        "t_resolved",
    )

    def __init__(
        self, query, runtime, fut, t_submit, tctx, deadline, tenant=None
    ):
        self.query = query
        self.runtime = runtime
        self.fut = fut
        self.t_submit = t_submit
        self.tctx = tctx
        self.deadline = deadline
        self.cancelled = False
        self.tenant = tenant
        self.held = False
        self.t_taken = self.t_closed = self.closed_by = None
        self.t_run = self.batch_size = self.dev_span_id = None
        self.t_dispatched = self.t_serve = self.t_served = None
        self.dispatch_error = self.serve_error = False
        self.t_resolved = None


class _BatchDispatcher:
    """Coalesces concurrent queries into one batch_predict device call.

    Handler threads submit a supplemented query and block on a Future; a
    single dispatcher thread assembles the queued queries into batches
    (`_collect` has the rule that closes one) and hands each to a
    `pipeline_depth`-wide worker pool. The pool is the pipelining seam
    (VERDICT r3 #3): the moment worker A's batch_predict has returned
    batch N's answers, the dispatcher thread closes batch N+1 and worker
    B puts it on the device while A still serves N's queries and wakes
    their handlers — the device never idles waiting for serve/JSON of a
    finished batch, and holds two batches' programs only when a backlog
    fills whole batches. A semaphore bounds the batches on the pool so
    queue pressure backs up into the drain loop instead of unbounded
    device memory. The reference never solved this (its serving hot path
    keeps the "TODO: Parallelize" comment, CreateServer.scala:514-517)."""

    def __init__(
        self,
        owner: "QueryServer",
        window_ms: float,
        max_batch: int,
        max_window_ms: Optional[float] = None,
        pipeline_depth: int = 4,
        batching: str = "continuous",
        tenant_drain: bool = True,
        admission_cap: int = 0,
    ):
        from concurrent.futures import ThreadPoolExecutor

        from predictionio_tpu.tenancy.fair import FairQueue

        if batching not in ("continuous", "windowed"):
            raise ValueError(
                f"batching must be continuous|windowed, got {batching!r}"
            )
        self.owner = owner
        self.min_window_s = window_ms / 1000.0
        self.max_window_s = (
            max_window_ms / 1000.0 if max_window_ms else self.min_window_s
        )
        self.window_s = self.min_window_s
        self.max_batch = max_batch
        self.batching = batching
        self.tenant_drain = tenant_drain
        self.admission_cap = max(0, int(admission_cap))
        self.pipeline_depth = max(1, pipeline_depth)
        self._pool = ThreadPoolExecutor(
            max_workers=self.pipeline_depth, thread_name_prefix="query-batch"
        )
        self._inflight = threading.BoundedSemaphore(self.pipeline_depth)
        self._active_lock = threading.Lock()
        self._active = 0  # guarded-by: _active_lock
        # the groups between hand-over and their answers (batch_predict
        # has not returned), by id(): continuous mode's close rule reads
        # whether there is one (ISSUE 38)
        self._awaiting: set[int] = set()  # guarded-by: _active_lock
        self.last_batch_sec = 0.0
        self._last_dispatch = 0.0  # windowed mode's pause heuristic
        registry = getattr(owner, "metrics", None)
        self._closed_counter = None if registry is None else registry.counter(
            "dispatch_batches_closed_total",
            "batches the dispatcher closed, by the branch of the close "
            "rule that closed them",
            ("closed_by",),  # label-bound: _collect's five literals
        )
        # weighted-fair queueing (ISSUE 6): per-tenant sub-queues drained
        # by deficit round robin replace the single FIFO, so one hog
        # tenant's backlog cannot starve the batch assembler. With no
        # tenants (every entry untenanted) this degenerates to FIFO.
        self._queue = FairQueue(
            weight_of=getattr(owner, "tenant_weight", None)
        )
        # the queries this dispatcher holds: submitted, not yet answered
        # or abandoned. Each stretch at zero is the span
        # `dispatch.no_work` (ISSUE 37): the share of an idle chip that
        # is the traffic's, not the program's
        self._held_lock = threading.Lock()
        self._held = 0  # guarded-by: _held_lock
        self._idle_since: Optional[float] = time.monotonic()  # guarded-by: _held_lock
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._loop, name="query-batcher", daemon=True
        )
        self._thread.start()

    def submit(
        self, query: Any, runtime: "EngineRuntime", timeout: float = 30.0,
        deadline: Optional[float] = None, tenant: Optional[str] = None,
    ) -> Any:
        """Submit with the runtime snapshot the handler extracted the query
        against — a /reload mid-window must not serve an old-typed query
        with the new model. The handler thread's trace/span context rides
        along so the dispatcher can attribute its queue/device/serve child
        spans to the right request.

        `deadline` (absolute time.monotonic()) caps the wait; when it
        passes — or `timeout` elapses — the entry is marked cancelled so
        the drain loop skips it instead of still dispatching it to the
        device (the old timeout leak), and DeadlineExceeded surfaces to
        the handler as a 503 + Retry-After."""
        import time as _t
        from concurrent.futures import Future, TimeoutError as _FutTimeout

        fut: Future = Future()
        tctx = (_tracing.current_trace_id(), _spans.current_span_id())
        p = _Pending(
            query, runtime, fut, time.perf_counter(), tctx, deadline, tenant
        )
        self._hold(p)
        self._queue.put(p)
        wait = timeout
        if deadline is not None:
            wait = min(wait, max(0.0, deadline - _t.monotonic()))
        t_woke = None
        try:
            # opened AFTER tctx was taken: the dispatcher's per-query
            # spans stay children of the request's own span, beside this
            # one
            with _spans.span("query.wait", server="query"):
                try:
                    return fut.result(timeout=wait)
                except _FutTimeout:
                    p.cancelled = True  # drain must not burn device time on this
                    self._release(p)
                    raise DeadlineExceeded(
                        "query abandoned: deadline passed while queued for "
                        "dispatch"
                    )
                finally:
                    t_woke = time.perf_counter()  # inside query.wait
        finally:
            if not p.cancelled:
                self._record_query_spans(p, t_woke)

    def _record_query_spans(self, p: _Pending, t_woke: float) -> None:
        """The query's wait, span by span, from the stamps its entry
        gathered — built HERE, by the handler thread once its future is
        done (ISSUE 37): on the request's own thread and in its own
        trace before the root span finalizes it; off the batch's
        critical path (a record a query on the worker, under the
        recorder's lock, is paid by every query of the batch) and,
        behind an HTTP handler, off the reply's too (`spans.defer`:
        recorded after the last byte). All of them lie inside the
        request's `query.wait`.

        `batch.queue_wait` (submit -> `_run_group` starts; it feeds
        batch_queue_wait_seconds through the recorder's bridge) is the
        sum, to the clock's resolution, of `batch.pickup` (put -> the
        loop thread takes the entry), `batch.assemble` (taken -> its
        batch closes; `closed_by` says which branch of `_collect` closed
        it) and `batch.slot_wait` (closed -> a pool thread runs it: the
        `_inflight` semaphore and the pool). Then `batch.device_dispatch`
        (all of batch_predict, under the span id the batch's storage
        RPCs were parented to), `batch.result_transfer` (this query's
        turn in the serve loop) and `query.wake` (future set -> this
        thread running again)."""
        tid, parent = p.tctx
        if tid is None or p.t_run is None:
            return
        # one reading of the three clocks places every stamp on the
        # epoch (the trace store's) and on time.monotonic() (stats()')
        now_wall, now_mono, now = (
            time.time(), time.monotonic(), time.perf_counter()
        )
        children: list = []

        def child(name: str, start: float, end: float,
                  span_id: Optional[str] = None, error: bool = False,
                  **attrs: Any) -> None:
            children.append(_spans.Span(
                trace_id=tid,
                span_id=span_id or _spans.new_span_id(),
                parent_span_id=parent,
                name=name, start=now_wall - (now - start),
                start_mono=now_mono - (now - start), duration=end - start,
                attrs={
                    "server": "query", "batch_size": p.batch_size, **attrs
                },
                error=error,
            ))

        child("batch.queue_wait", p.t_submit, p.t_run)
        if p.t_taken is not None and p.t_closed is not None:
            child("batch.pickup", p.t_submit, p.t_taken)
            child("batch.assemble", p.t_taken, p.t_closed,
                  closed_by=p.closed_by)
            child("batch.slot_wait", p.t_closed, p.t_run)
        if p.t_dispatched is not None:
            child("batch.device_dispatch", p.t_run, p.t_dispatched,
                  span_id=p.dev_span_id, error=p.dispatch_error)
        if p.t_served is not None:
            child("batch.result_transfer", p.t_serve, p.t_served,
                  error=p.serve_error)
        if p.t_resolved is not None:
            child("query.wake", p.t_resolved, t_woke)
        # held for the HTTP handler to record after the reply's last
        # byte, under one hold of the recorder's lock (recorded here and
        # now where no handler serves): the handler threads of a batch
        # wake together, and seven contended acquisitions a query before
        # the reply cost serve-sharded's median 3.6 % (PERF.md, PR 37)
        _spans.defer(children)

    # -- the count of held queries (`dispatch.no_work`) ----------------------
    def _hold(self, p: _Pending) -> None:
        """Count a submitted entry, before it is queued; the entry that
        ends a stretch with none held records the stretch."""
        now = time.monotonic()
        with self._held_lock:
            idle_from, self._idle_since = self._idle_since, None
            self._held += 1
            p.held = True
        if idle_from is not None:
            self._record_no_work(idle_from, now)

    def _release(self, p: _Pending) -> None:
        """Let go of an entry that was answered or abandoned (once; an
        entry put on the queue by hand was never counted)."""
        with self._held_lock:
            if not p.held:
                return
            p.held = False
            self._held -= 1
            if self._held == 0:
                self._idle_since = time.monotonic()

    def _flush_no_work(self) -> None:
        """Record the stretch with no query held so far and start the
        next piece at this instant: the idle loop thread does this every
        0.2 s (and `stop()` once), so a piece lands in the second of
        `stats()` it was spent in however long the server stays empty."""
        now = time.monotonic()
        with self._held_lock:
            if self._idle_since is None:
                return
            idle_from, self._idle_since = self._idle_since, now
        self._record_no_work(idle_from, now)

    @staticmethod
    def _record_no_work(start_mono: float, end_mono: float) -> None:
        # after the fact (the stretch starts on a worker's thread and
        # ends on a handler's, so no annotation can cover it), as a
        # state span: in stats(), in no trace
        if end_mono <= start_mono:
            return
        _spans.get_default_recorder().record(_spans.Span(
            trace_id=_spans.NO_TRACE, span_id=_spans.new_span_id(),
            name="dispatch.no_work",
            start=time.time() - (time.monotonic() - start_mono),
            duration=end_mono - start_mono, start_mono=start_mono,
            attrs={"server": "query"},
        ))

    def _resolve(self, p: _Pending, result: Any = None,
                 exc: Optional[BaseException] = None) -> None:
        """Answer an entry, once: stamp the instant (its `query.wake`
        starts here), let go of it, wake its handler."""
        if p.fut.done():
            return
        p.t_resolved = time.perf_counter()
        self._release(p)
        if exc is not None:
            p.fut.set_exception(exc)
        else:
            p.fut.set_result(result)

    def stop(self) -> None:
        self._stop.set()
        self._queue.wake()  # the loop thread sleeps on the queue's condition
        self._thread.join(timeout=1.0)
        self._pool.shutdown(wait=False)
        # fail any waiters still queued so their handler threads don't
        # block out the full submit timeout
        import queue as _q

        while True:
            try:
                p = self._queue.get_nowait()
            except _q.Empty:
                break
            self._resolve(p, exc=RuntimeError("query server stopped"))
        self._flush_no_work()

    def _run_group(self, rt: "EngineRuntime", group: list) -> None:
        # last-chance shed: entries can be cancelled (or expire) while
        # the batch waits on the backpressure semaphore — re-filter at
        # the moment device time is about to be spent (ISSUE 4). In
        # place: the list is the one the hand-over counted (`_awaiting`)
        group[:] = self._shed_dead(group)
        if not group:
            return
        queries = [(i, p.query) for i, p in enumerate(group)]
        t0 = time.perf_counter()
        registry = getattr(self.owner, "metrics", None)
        recorder = _spans.get_default_recorder()
        # query-triggered capture (ISSUE 8 satellite): an armed
        # POST /debug/traces/capture spends one batch credit here and
        # force-retains every trace riding this batch — the operator's
        # "trace the next N batches" regardless of PIO_TRACE_SAMPLE
        capture_id = recorder.consume_capture()
        if capture_id is not None:
            for p in group:
                if p.tctx[0]:
                    recorder.force_keep(p.tctx[0], capture_id)
        # pre-mint the per-query device span ids: storage RPCs issued
        # DURING batch_predict (e.g. UR history fetches) must parent
        # under a device span, so its id has to exist before the call.
        # The per-query spans themselves are built from the entry's
        # stamps by its handler thread (`_record_query_spans`): nothing
        # is recorded a query on this thread, where the batch waits
        dev_ids = [
            _spans.new_span_id() if p.tctx[0] else None for p in group
        ]
        for p, dev_id in zip(group, dev_ids):
            p.t_run, p.batch_size, p.dev_span_id = t0, len(group), dev_id
        if registry is not None:
            registry.histogram(
                "batch_size", "queries per coalesced device batch",
                buckets=BATCH_SIZE_BUCKETS, lower_bound=1,
            ).observe(len(group))
        # batch-level work (one device program for the whole group) runs
        # under the FIRST traced query's context: its device span adopts
        # any storage RPC spans the batch's predict issues. One batch,
        # many traces — the representative trace gets the full picture,
        # the rest still see their own queue/device/serve timings.
        rep = next((i for i, d in enumerate(dev_ids) if d), None)
        tok_t = tok_s = None
        if rep is not None:
            tok_t = _tracing.set_trace_id(group[rep].tctx[0])
            tok_s = _spans.set_current_span(dev_ids[rep])
        # padding-waste accounting (ISSUE 3) is recorded at the PAD SITES
        # this dispatch drives (engines' _predict_batch, the only places
        # that know the vocab-known row count and the actual bucket) —
        # each batch_predict below lands batch_padding_ratio samples and
        # wasted-FLOPs on the process-default registry.
        # the group's variant scopes the fault point below and attributes
        # fallback errors to the right canary window (ISSUE 5); duck-typed
        # like count_shed — test harnesses drive this loop with minimal
        # owner doubles
        variant_of = getattr(self.owner, "variant_of", None)
        variant = variant_of(rt) if variant_of is not None else "live"
        # groups are keyed by runtime snapshot and each tenant serves its
        # own runtime, so a group is (at most) one tenant's batch — its
        # id scopes the fault point and the device-seconds charge below
        group_tenant = group[0].tenant if group else None
        try:
            try:
                # fault point (ISSUE 4): "error" fails the batch into the
                # per-query fallback below; "delay" simulates a slow
                # device, which is what deadline shedding exists for.
                # The scope label (ISSUE 5) lets chaos tests target one
                # rollout variant: `dispatch.device@candidate:...` flips
                # only canary batches bad while live batches sail through
                _faults.fire("dispatch.device", scope=variant)
                if group_tenant:
                    # per-tenant fault scope (ISSUE 6): chaos tests flip
                    # ONE tenant's batches bad
                    # (`dispatch.device@tenant/acme:...`) while every
                    # other tenant keeps serving
                    _faults.fire(
                        "dispatch.device",
                        scope=f"tenant/{group_tenant}", scoped_only=True,
                    )
                # the batch's own work is real spans on this thread
                # (ISSUE 25): a profiler trace shows them, stats() sums
                # them, and the algorithm's spans nest under them
                with _spans.span(
                    "batch.predict", server="query", batch_size=len(group),
                ) as predict_sp:
                    compiles = compile_snapshot()[0]
                    per_algo = [
                        dict(algo.batch_predict(
                            algo.serving_context, model, queries
                        ))
                        for algo, model in zip(rt.algorithms, rt.models)
                    ]
                    predict_sp.attrs["jit_compiles"] = (
                        compile_snapshot()[0] - compiles
                    )
                t_done = time.perf_counter()
                # the answers are host arrays and the device is free: the
                # assembling batch closes on this (ISSUE 38), and what is
                # left of this one — the bookkeeping, every query's
                # serve, its future, its handler's wake — runs on this
                # slot of the pool beside the next batch's put, launch
                # and program. `batch.finish` is that stretch
                self._answers_ready(group)
                with _spans.state_span(
                    "batch.finish", server="query", batch_size=len(group),
                ):
                    self._finish_group(rt, group, per_algo, t_done, registry)
            except Exception:
                # one bad query must not poison the batch: retry
                # individually so each waiter gets its own result or its
                # own error. The failed device span is recorded errored
                # so tail sampling always retains these traces.
                t_failed = time.perf_counter()
                for p in group:
                    if not p.fut.done():
                        p.t_dispatched, p.dispatch_error = t_failed, True
                charge = getattr(
                    self.owner, "charge_device_seconds", None
                )
                for p in group:
                    if p.cancelled:  # client gone mid-batch: skip retry
                        continue
                    t_q = time.perf_counter()
                    try:
                        # scoped_only: a scope-less dispatch.device spec
                        # keeps the PR-4 semantic (batch fails, per-query
                        # fallback succeeds); a variant-scoped spec also
                        # fails the fallback so the targeted variant's
                        # queries error visibly — the canary verdict's
                        # error-rate input
                        _faults.fire(
                            "dispatch.device", scope=variant,
                            scoped_only=True,
                        )
                        if p.tenant:
                            _faults.fire(
                                "dispatch.device",
                                scope=f"tenant/{p.tenant}",
                                scoped_only=True,
                            )
                        predictions = [
                            algo.predict(model, p.query)
                            for algo, model in zip(rt.algorithms, rt.models)
                        ]
                        self._resolve(
                            p, result=rt.serving.serve(p.query, predictions)
                        )
                    except Exception as e:
                        self._resolve(p, exc=e)
                    finally:
                        # fallback predicts are real device work: debit
                        # the post-paid device-seconds bucket here too,
                        # or a tenant whose queries poison every batch
                        # (forcing this path) would bypass the exact
                        # quota meant to contain it
                        if charge is not None and p.tenant:
                            charge(
                                p.tenant, time.perf_counter() - t_q
                            )
        finally:
            if tok_s is not None:
                _spans.reset_current_span(tok_s)
            if tok_t is not None:
                _tracing.reset_trace_id(tok_t)

    def _finish_group(
        self, rt: "EngineRuntime", group: list, per_algo: list,
        t_done: float, registry: Any,
    ) -> None:
        """What is left of a batch once batch_predict has returned its
        answers at `t_done`: its bookkeeping, then every query's serve
        and future. The span `batch.finish` (`_run_group`) covers it."""
        self.last_batch_sec = t_done - group[0].t_run
        for p in group:
            p.t_dispatched = t_done
        if registry is not None:
            # one observation per coalesced BATCH (the per-query device
            # spans share its wall time; bridging them would inflate the
            # count). The name says "device"; what it times is all of
            # batch_predict on the host's clock — lookups, the device
            # pass, the decode. The device pass alone is
            # als.predict.device.
            registry.histogram(
                "batch_device_seconds",
                "wall time of batch_predict per coalesced batch: "
                "host lookups, device pass and decode",
            ).observe(self.last_batch_sec)
        self.owner.bookkeep_predict(self.last_batch_sec, len(group))
        # per-tenant device-seconds accounting (ISSUE 6): each tenant in
        # the batch is charged its per-query share of the measured
        # device time — the post-paid debit the device-seconds quota
        # enforces at the next admission
        charge = getattr(self.owner, "charge_device_seconds", None)
        if charge is not None and group[0].tenant is not None:
            per_query = self.last_batch_sec / len(group)
            counts: dict[str, int] = {}
            for p in group:
                if p.tenant:
                    counts[p.tenant] = counts.get(p.tenant, 0) + 1
            for tid, n in counts.items():
                charge(tid, per_query * n)
        # a trace of its own: the first reply below lets its request
        # finish, and sample, while this loop still runs
        with _spans.detached(), _spans.span(
            "batch.serve", server="query", batch_size=len(group),
        ):
            for i, p in enumerate(group):
                # result-transfer/serve: per-query fetch + combinator,
                # between the two stamps
                p.t_serve = time.perf_counter()
                try:
                    result = rt.serving.serve(
                        p.query, [pa[i] for pa in per_algo]
                    )
                except Exception as e:  # serve failure is per-query
                    p.t_served = time.perf_counter()
                    p.serve_error = True
                    self._resolve(p, exc=e)
                    continue
                p.t_served = time.perf_counter()
                self._resolve(p, result=result)

    def _loop(self) -> None:
        import queue as _q

        while not self._stop.is_set():
            try:
                first = self._queue.get(timeout=0.2, unless=self._stop.is_set)
            except _q.Empty:
                self._flush_no_work()
                continue
            first.t_taken = time.perf_counter()
            # the loop thread's own two states, a state span each a batch
            # (ISSUE 37; in stats() and on a profiler trace, in no
            # request's trace): collecting — the first entry taken until
            # the batch closes — and handing the closed batch over — the
            # wait for an `_inflight` slot. Under a profiler the idle
            # chip of those seconds carries the dispatcher's state, not
            # the name of whichever handler thread waits shortest
            with _spans.state_span("dispatch.collect", server="query") as sp:
                batch, closed_by = self._collect(first)
                sp.attrs["size"] = len(batch)
                sp.attrs["closed_by"] = closed_by
            if self._closed_counter is not None:
                self._closed_counter.inc(closed_by=closed_by)
            with _spans.state_span("dispatch.slot", server="query"):
                t_closed = time.perf_counter()
                for p in batch:
                    p.t_closed, p.closed_by = t_closed, closed_by
                self._hand_over(batch)

    def _collect(self, first: _Pending) -> tuple[list, str]:
        """Assemble one batch around `first`; returns it with the branch
        that closed it (`closed_by`: on `dispatch.collect`, on every
        query's `batch.assemble`, in `dispatch_batches_closed_total`).

        Continuous mode (the default) closes a batch on events (ISSUE
        38). It takes everything already queued, and then:

        - `idle_pipeline`: the queue is dry and no batch is between
          hand-over and its answers — close NOW. Any wait is dead time
          on an idle device: a lone query on an idle pipeline is handed
          over without a timed wait in front of it.
        - `answers_ready`: a batch is awaiting its answers, so the
          device is taken and closing early would serve nobody sooner —
          keep ADMITTING arrivals into this assembling batch until that
          batch's batch_predict has returned (`_answers_ready`), then
          close. The batch ahead finishes (`batch.finish`) on its own
          slot while this one runs: at most one batch awaits its answers
          at a time, `_inflight` bounds those that are finishing.
        - `full`: the batch reached max_batch, and is handed over
          whatever is awaited: under a backlog that fills batches up to
          pipeline_depth of them are on the pool, their programs queued
          on the device stream.
        - `wedge`: the answers did not come within 10 × max_window (or
          1.2 × the last batch, if longer: device hang, accounting leak)
          — the ONE timed wait of the mode, the timeout of the sleep
          below; shed_dead and the clients' own deadlines still bound
          how long a held query can suffer.

        The loop thread sleeps on the queue's condition, which an
        arrival (`put`) and a batch's answers (`wake`) both notify: no
        window, no poll. Windowed mode (`window`; ROADMAP D4) shares no
        rule with this: `_collect_windowed`."""
        import queue as _q

        if self.batching == "windowed":
            return self._collect_windowed(first)
        batch = [first]
        wedge_deadline = time.monotonic() + max(
            10.0 * self.max_window_s, self.last_batch_sec * 1.2
        )
        waited = False
        while len(batch) < self.max_batch:
            # the admission cap applies however the batch closes: a
            # capped tenant's overflow waits for the next one
            skip = self._admission_skip(batch)
            try:
                self._take(batch, self._queue.get_nowait(skip=skip))
                continue
            except _q.Empty:
                pass
            if self._none_awaited():
                return batch, "answers_ready" if waited else "idle_pipeline"
            remaining = wedge_deadline - time.monotonic()
            if remaining <= 0:
                return batch, "wedge"
            waited = True
            try:
                self._take(batch, self._queue.get(
                    timeout=remaining, skip=skip, unless=self._none_awaited
                ))
            except _q.Empty:
                pass
        return batch, "full"

    @staticmethod
    def _take(batch: list, p: _Pending) -> None:
        p.t_taken = time.perf_counter()
        batch.append(p)

    def _none_awaited(self) -> bool:
        """No batch is between hand-over and its answers (or the
        dispatcher is stopping): what ends the assembling batch's wait.
        Read under the queue's lock by its `get`; `_answers_ready` and
        `stop` change it and then `wake` the queue."""
        with self._active_lock:
            return not self._awaiting or self._stop.is_set()

    def _answers_ready(self, group: list) -> None:
        """`group` no longer awaits its answers: its batch_predict has
        returned — or it ended without (every entry shed, or the batch
        failed and its per-query fallback, which runs programs of its
        own, is through). Once a group; wakes the loop thread."""
        with self._active_lock:
            if id(group) not in self._awaiting:
                return
            self._awaiting.remove(id(group))
        self._queue.wake()

    def _collect_windowed(self, first: _Pending) -> tuple[list, str]:
        """Windowed mode's drain (the PR-2 behavior; ROADMAP D4), on its
        own timers: with nothing in flight dispatch once the arrival
        stream pauses (`idle_pipeline`); with batches in flight linger
        up to max_window / 1.2 × the last batch for more arrivals
        (`window`). With tenants active, the tenant_drain knob ends the
        linger as soon as every still-backlogged tenant is represented
        in the bucket — one group per tenant per round beats a full
        bucket for fairness latency. `full`: max_batch."""
        import queue as _q

        batch = [first]
        round_t0 = time.monotonic()
        hard_deadline = time.monotonic() + max(
            self.max_window_s, self.last_batch_sec * 1.2
        )
        while len(batch) < self.max_batch:
            try:
                self._take(batch, self._queue.get_nowait())
                continue
            except _q.Empty:
                pass
            with self._active_lock:
                active = self._active
            if active == 0:
                # pipeline idle: dispatch once the arrival stream
                # pauses. Under recent load the pause threshold
                # scales with the measured batch time (a closed-loop
                # response burst spreads over tens of ms; splitting
                # it costs a full device round-trip per fragment);
                # after a quiet second it drops back to min_window so
                # sporadic queries keep near-zero added latency.
                patience = self.min_window_s
                if time.monotonic() - self._last_dispatch < 1.0:
                    patience = max(
                        patience, min(0.1 * self.last_batch_sec, 0.02)
                    )
                try:
                    self._take(batch, self._queue.get(timeout=patience))
                    continue
                except _q.Empty:
                    return batch, "idle_pipeline"
            if self.tenant_drain and (
                time.monotonic() - round_t0 >= self.min_window_s
            ):
                # only after the base window: closing on a
                # momentarily-dry queue would ship one-tenant
                # rounds before the other tenants' arrivals land
                backlog = self._queue.backlogged()
                present = {p.tenant for p in batch}
                tenancy_active = bool(
                    (present | backlog) - {None}
                )
                if tenancy_active and backlog <= present:
                    # every backlogged tenant has a group
                    return batch, "window"
            remaining = hard_deadline - time.monotonic()
            if remaining <= 0:
                return batch, "window"
            try:
                self._take(
                    batch, self._queue.get(timeout=min(remaining, 0.002))
                )
            except _q.Empty:
                pass
        return batch, "full"

    def _hand_over(self, batch: list) -> None:
        """A closed batch onto the pool, a group a runtime, each behind
        the `_inflight` semaphore."""
        self.window_s = self.min_window_s  # status display only
        # drain-time shedding (ISSUE 4): entries whose client already
        # gave up (cancelled) or whose deadline passed while queued
        # are dropped HERE — before the backpressure semaphore and
        # the device dispatch, which is exactly the time they'd waste
        ready = self._shed_dead(batch)
        # group by runtime snapshot: queries spanning a /reload are
        # served by the runtime they were extracted against
        groups: dict[int, tuple[Any, list]] = {}
        for p in ready:
            groups.setdefault(id(p.runtime), (p.runtime, []))[1].append(p)
        for rt, group in groups.values():
            # poll the semaphore so a stop() during backpressure
            # doesn't leave this thread blocked forever
            acquired = False
            while not self._stop.is_set():
                if self._inflight.acquire(timeout=0.2):
                    acquired = True
                    break
            if acquired:
                try:
                    with self._active_lock:
                        self._active += 1
                        self._awaiting.add(id(group))
                    self._last_dispatch = time.monotonic()
                    self._pool.submit(
                        self._run_group_released, rt, group
                    )
                    continue
                except RuntimeError:  # pool already shut down
                    with self._active_lock:
                        self._active -= 1
                        self._awaiting.discard(id(group))
                    self._inflight.release()
            for p in group:
                self._resolve(p, exc=RuntimeError("query server stopped"))

    def _admission_skip(self, batch: list) -> Optional[set]:
        """Tenants whose slots in the ASSEMBLING bucket are used up
        (ISSUE 14 satellite — adaptive continuous-batching admission).
        Only continuous mode caps (windowed mode's drain never asks),
        and only with more than one active stream: a solo tenant (or
        untenanted traffic alone) keeps the whole bucket. Auto cap =
        max_batch // active streams."""
        counts: dict = {}
        for p in batch:
            counts[p.tenant] = counts.get(p.tenant, 0) + 1
        active = set(counts) | self._queue.backlogged()
        if len(active) <= 1 or not (active - {None}):
            return None
        cap = self.admission_cap or max(
            1, self.max_batch // len(active)
        )
        skip = {t for t, c in counts.items() if c >= cap}
        return skip or None

    def _shed_dead(self, entries: list) -> list:
        """Drop cancelled/deadline-expired entries, failing their futures
        with DeadlineExceeded (→ 503 + Retry-After at the handler) and
        counting the shed. Returns the still-live entries."""
        import time as _t

        now_m = _t.monotonic()
        live = []
        for p in entries:
            if p.cancelled or (
                p.deadline is not None and now_m >= p.deadline
            ):
                self._resolve(p, exc=DeadlineExceeded(
                    "deadline expired before device dispatch"
                ))
                shed = getattr(self.owner, "count_shed", None)
                if shed is not None:
                    shed("cancelled" if p.cancelled else "expired_in_queue")
                continue
            live.append(p)
        return live

    def _run_group_released(self, rt: "EngineRuntime", group: list) -> None:
        try:
            self._run_group(rt, group)
        finally:
            # a group that ended without answers must not hold the
            # assembling batch (a no-op where batch_predict returned)
            self._answers_ready(group)
            with self._active_lock:
                self._active -= 1
            self._inflight.release()


class RolloutConflict(RuntimeError):
    """A rollout operation conflicts with the server's current state
    (one already active, or none to abort) — a 409 at the HTTP edge."""


class _Server(ThreadedServer):
    owner: "QueryServer"


class QueryServer(ServerProcess):
    """Deploy-server process: serves one engine variant's latest model."""

    _name = "query-server"

    def __init__(
        self,
        storage: Storage,
        runtime: EngineRuntime,
        config: Optional[QueryServerConfig] = None,
    ):
        super().__init__()
        self.storage = storage
        self.runtime = runtime
        self.config = config or QueryServerConfig()
        self.output_blockers = [
            p for p in self.config.plugins
            if getattr(p, "plugin_type", "") == OUTPUT_BLOCKER
        ]
        self.output_sniffers = [
            p for p in self.config.plugins
            if getattr(p, "plugin_type", "") == OUTPUT_SNIFFER
        ]
        # observability (ISSUE 1): registry histograms replace the
        # reference's lossy running averages (CreateServer.scala:603-610)
        # — the old request_count/avg_* attributes survive as properties
        # derived from the histograms, so nothing downstream loses its API
        self.metrics = server_registry()
        self._serve_hist = self.metrics.histogram(
            "serve_seconds",
            "end-to-end query serve time (parse to response JSON built)",
        )
        self._predict_hist = self.metrics.histogram(
            "predict_seconds",
            "device-side predict time per query (model compute + fetch)",
        )
        # span→metric bridge (ISSUE 2): the dispatcher's queue-wait SPAN
        # is the single source — its duration feeds this histogram, so
        # /metrics aggregates and /debug/traces exemplars can't drift
        self._queue_wait_hist = self.metrics.histogram(
            "batch_queue_wait_seconds",
            "micro-batch queue wait, submit to device dispatch",
        )
        # one bridge per span name on the process recorder: with two
        # live QueryServers in one process the newest wins; stop()
        # unregisters so a stopped server's registry isn't kept alive
        self._queue_wait_bridge = (
            lambda sp, _h=self._queue_wait_hist: _h.observe(sp.duration)
        )
        _spans.get_default_recorder().bridge(
            "batch.queue_wait", self._queue_wait_bridge
        )
        # the sharded tier's batches and exclusion bytes, counted off
        # its own `sharded.dispatch` span the same way (ISSUE 27); the
        # two counters are mounted on this registry by the first such
        # span, so only where a ShardedRuntime serves (ISSUE 37)
        from predictionio_tpu.fleet import bridge_sharded_metrics

        self._sharded_bridge = bridge_sharded_metrics(self.metrics)
        # load shedding (ISSUE 4): expired/abandoned queries refused
        # before device time, by reason
        self._shed_counter = self.metrics.counter(
            "queries_shed_total",
            "queries shed before device dispatch (503 + Retry-After)",
            ("reason",),  # label-bound: literal shed-reason set
        )
        # canary rollout (ISSUE 5): per-variant serve/error metrics under
        # a `variant` label — p99s come from the labeled histogram, the
        # verdict loop reads its own sliding windows
        self._variant_serve_hist = self.metrics.histogram(
            "variant_serve_seconds",
            "end-to-end serve time by rollout variant",
            ("variant",),  # label-bound: literal live|candidate
        )
        self._variant_requests = self.metrics.counter(
            "variant_requests_total", "queries served by rollout variant",
            ("variant",),  # label-bound: literal live|candidate
        )
        self._variant_errors = self.metrics.counter(
            "variant_errors_total",
            "failed queries (4xx/5xx/shed) by rollout variant",
            ("variant",),  # label-bound: literal live|candidate
        )
        # runtime-swap lock (ISSUE 5 satellite): /reload and rollout
        # promote/abort all mutate the served-runtime references; the
        # lock serializes them so two concurrent reloads cannot
        # interleave build_runtime with the swap
        self._swap_lock = threading.RLock()
        # sanitizer: reload/promote intentionally hold the swap lock
        # across the candidate's device-staging build (two concurrent
        # reloads must serialize); the SERVING path never takes this
        # lock — queries ride runtime snapshots — so nothing user-facing
        # blocks behind it
        _tsan.allow_blocking_lock(self._swap_lock)
        self.candidate: Optional[EngineRuntime] = None  # guarded-by: _swap_lock
        self.rollout = None  # Optional[RolloutController]  # guarded-by: _swap_lock
        self.tenancy = None  # Optional[TenantMux] (ISSUE 6)
        self.online = None  # Optional[OnlineConsumer] (ISSUE 9)
        self.replica = None  # Optional[ReplicaMember] (ISSUE 15)
        # in-flight query count (ISSUE 15): graceful drain waits on it
        self._inflight_lock = threading.Lock()
        self._inflight = 0  # guarded-by: _inflight_lock
        # in-flight tenant-prefetch warm threads (ISSUE 15): tracked so
        # stop() joins them, same discipline as the feedback threads
        self._prefetch_lock = threading.Lock()
        self._prefetch_threads: set[threading.Thread] = set()  # guarded-by: _prefetch_lock
        self.last_serving_sec = 0.0
        self.last_predict_sec = 0.0
        # in-flight feedback POST threads: tracked so stop() joins them
        # (ISSUE 12 thread-lifecycle — the old per-feedback spawn could
        # outlive the server and POST into a torn-down event server)
        self._feedback_lock = threading.Lock()
        self._feedback_threads: set[threading.Thread] = set()  # guarded-by: _feedback_lock
        self.dispatcher: Optional[_BatchDispatcher] = None
        if self.config.micro_batch:
            self.dispatcher = _BatchDispatcher(
                self,
                self.config.batch_window_ms,
                self.config.max_batch,
                self.config.max_window_ms,
                self.config.pipeline_depth,
                batching=getattr(self.config, "batching", "continuous"),
                tenant_drain=getattr(self.config, "tenant_drain", True),
                admission_cap=getattr(self.config, "admission_cap", 0),
            )

    def start(self) -> int:
        port = super().start()
        # rollout re-adoption (ISSUE 6 satellite, PR-5 follow-up): a
        # restart mid-canary re-adopts the persisted bake instead of
        # silently dropping it (tenant rollouts re-adopt in the mux's
        # sync pass; this covers the server's own engine variant)
        try:
            from predictionio_tpu.deploy.rollout import resume_rollout

            resume_rollout(self)
        except Exception:
            log.exception("rollout re-adoption failed; serving continues")
        return port

    def stop(self) -> None:
        if self.replica is not None:
            # deregister + join the heartbeat thread BEFORE the server
            # goes down, so the gateway never routes to a dead port
            # that still looks alive in the registry
            self.replica.stop()
        if self.online is not None:
            # the consumer thread joins on server stop — same discipline
            # as the monitor/mux/dispatcher threads (ISSUE 9 CI guard)
            self.online.stop()
        if self.tenancy is not None:
            self.tenancy.stop()
        if self.rollout is not None:
            self.rollout.stop()
        if self.dispatcher is not None:
            self.dispatcher.stop()
        _spans.get_default_recorder().unbridge(
            "batch.queue_wait", self._queue_wait_bridge
        )
        _spans.get_default_recorder().unbridge(
            "sharded.dispatch", self._sharded_bridge
        )
        with self._feedback_lock:
            pending_feedback = list(self._feedback_threads)
        for t in pending_feedback:
            t.join(timeout=11)  # POST timeout is 10s
        with self._prefetch_lock:
            pending_prefetch = list(self._prefetch_threads)
        for t in pending_prefetch:
            t.join(timeout=5)
        super().stop()  # also detaches the log shipper (ServerProcess)

    def _make_server(self) -> _Server:
        server = _Server((self.config.ip, self.config.port), _Handler)
        server.owner = self
        server.metrics = self.metrics
        server.metrics_label = "query"
        # identity attrs for every server span this process emits
        # (ISSUE 16): after the fleet collector stitches this replica's
        # spans into a cross-process tree, "which engine answered" must
        # survive without a lookup. ReplicaMember.start merges the
        # replica id into this same dict.
        inst = getattr(self.runtime, "instance", None)
        if inst is not None:
            server.span_attrs = {
                "engine": f"{inst.engine_id}/{inst.engine_variant}",
            }
        return server

    # -- reload (reference MasterActor ReloadServer, CreateServer.scala:337) --
    def reload(self) -> None:
        """Hot-swap to the latest COMPLETED instance; in-flight queries keep
        the old runtime snapshot. Serialized under the runtime-swap lock
        (ISSUE 5 satellite): two concurrent reloads — or a reload racing
        a rollout promote — must not interleave build_runtime with the
        reference swap."""
        with self._swap_lock:
            rollout = self.rollout
            if rollout is not None and rollout.st.state in (
                "starting", "canary"
            ):
                # a reload would silently change the verdict baseline
                # mid-bake AND be overwritten by the promote swap —
                # abort the canary first, then reload
                raise RolloutConflict(
                    f"rollout of {rollout.st.version.id} is active; "
                    "abort it before reloading"
                )
            inst = self.runtime.instance
            new_runtime = latest_completed_runtime(
                self.storage, inst.engine_id, inst.engine_version,
                inst.engine_variant,
            )
            self.runtime = new_runtime  # atomic reference swap

    def count_shed(self, reason: str) -> None:
        self._shed_counter.inc(reason=reason)

    # -- canary rollout (ISSUE 5) ------------------------------------------
    def pick_runtime(
        self, raw_request: bytes, bucket: Optional[int] = None
    ) -> tuple[EngineRuntime, str]:
        """Route one request: a sticky hash-of-request fraction lands on
        the candidate while a non-shadow rollout is active. Snapshot the
        references ONCE — a concurrent swap must not split a request
        across two runtimes. `bucket` (ISSUE 15) is the gateway's
        pre-computed routing hash when one fronts this replica."""
        from predictionio_tpu.deploy.rollout import sticky_candidate

        candidate, rollout = self.candidate, self.rollout
        if (
            candidate is not None
            and rollout is not None
            and not rollout.config.shadow
            and sticky_candidate(
                raw_request, rollout.config.fraction, bucket=bucket
            )
        ):
            return candidate, "candidate"
        return self.runtime, "live"

    def variant_of(self, rt: EngineRuntime) -> str:
        if rt is self.candidate:
            return "candidate"
        mux = self.tenancy
        if mux is not None and mux.is_candidate(rt):
            return "candidate"
        return "live"

    # -- replica membership (ISSUE 15) -------------------------------------
    def inflight_enter(self) -> None:
        with self._inflight_lock:
            self._inflight += 1

    def inflight_exit(self) -> None:
        with self._inflight_lock:
            self._inflight -= 1

    @property
    def inflight_queries(self) -> int:
        with self._inflight_lock:
            return self._inflight

    def attach_replica(self, member) -> None:
        """Join the replicated serving tier: register the heartbeating
        replica record and adopt the durable replica identity — which
        also scopes any online fold-in cursor attached afterwards, so N
        replicas on one stream never share a cursor."""
        self.replica = member
        member.start()

    def replica_status(self) -> dict:
        if self.replica is None:
            return {"state": "detached"}
        return dict(self.replica.status(), state="attached")

    def prefetch_tenants(self, tenant_ids: list[str]) -> list[str]:
        """Warm the tenant model cache off the serving path (the
        gateway's scale-up hint). Best-effort: unknown tenants and
        failed loads are skipped — the replica must come up regardless."""
        mux = self.tenancy
        if mux is None or not tenant_ids:
            return []
        accepted = [str(t) for t in tenant_ids[:64]]

        def warm():
            try:
                for tid in accepted:
                    try:
                        tenant = mux.admit(tid)
                    except Exception:
                        log.debug(
                            "prefetch warm of tenant %r failed", tid,
                            exc_info=True,
                        )
                        continue
                    # admit holds a concurrency slot until done — a
                    # failed model load must still release it or the
                    # tenant's quota leaks one slot per failed warm
                    lease = None
                    try:
                        _rt, _variant, lease = mux.route(tenant, b"")
                    except Exception:
                        log.debug(
                            "prefetch warm of tenant %r failed", tid,
                            exc_info=True,
                        )
                    finally:
                        mux.done(tid, lease)
            finally:
                with self._prefetch_lock:
                    self._prefetch_threads.discard(
                        threading.current_thread()
                    )

        t = threading.Thread(
            target=warm, name="tenant-prefetch", daemon=True
        )
        with self._prefetch_lock:
            self._prefetch_threads.add(t)
        t.start()
        return accepted

    # -- online learning (ISSUE 9) -----------------------------------------
    def attach_online(
        self, app_id: int, config=None, channel_id: Optional[int] = None,
        consumer=None,
    ):
        """Attach a streaming fold-in consumer: events for `app_id` tail
        into this server's live runtime between retrains. Pass a
        pre-built `consumer` to override the default wiring (tests).

        With a replica member attached (ISSUE 15), the DEFAULT cursor
        record name gains the durable replica id — two replicas folding
        the same stream automatically use distinct single-writer
        cursors instead of relying on the operator to name them."""
        from predictionio_tpu.online import (
            OnlineConsumer,
            OnlineConsumerConfig,
            ServerApplyHost,
        )

        if self.online is not None:
            self.online.stop()
            if not self.online.stopped():
                # a wedged tick survived the stop timeout: starting a
                # replacement would put TWO writers on the same
                # single-writer cursor record
                raise RuntimeError(
                    "previous online consumer did not stop (wedged "
                    "tick?); refusing to start a second writer on its "
                    "cursor"
                )
        if consumer is None:
            config = config or OnlineConsumerConfig()
            if config.name is None and self.replica is not None:
                config = dataclasses.replace(
                    config,
                    name=(
                        f"online/{app_id}/server"
                        f"@{self.replica.replica_id}"
                    ),
                    # one-shot adoption of the pre-replica-scoped record
                    # (ISSUE 19 satellite): a server upgraded in place
                    # resumes exactly where its un-scoped cursor stood
                    migrate_from=(
                        config.migrate_from
                        or f"online/{app_id}/server"
                    ),
                )
            consumer = OnlineConsumer(
                self.storage, ServerApplyHost(self), app_id,
                config=config, channel_id=channel_id,
                metrics=self.metrics,
            )
        self.online = consumer
        self.online.start()
        return self.online

    def online_status(self) -> dict:
        if self.online is None:
            return {"state": "detached"}
        return dict(self.online.status(), state="attached")

    # -- sharded serving status (ISSUE 10) ---------------------------------
    def fleet_serving_status(self) -> dict:
        """GET /fleet/status: the sharded-serving layout of every served
        runtime (live + candidate). Snapshotted under the runtime-swap
        lock so a /reload or rollout promote mid-read can't tear the
        variant→model mapping."""
        with self._swap_lock:
            variants = {"live": self.runtime}
            if self.candidate is not None:
                variants["candidate"] = self.candidate
            out: dict = {"variants": {}}
            for name, rt in variants.items():
                models = []
                for model in getattr(rt, "models", ()) or ():
                    info_fn = getattr(model, "sharded_info", None)
                    info = info_fn() if callable(info_fn) else None
                    models.append(
                        {"sharded": info is not None, **(info or {})}
                    )
                out["variants"][name] = {"models": models}
            out["sharded"] = any(
                m["sharded"]
                for v in out["variants"].values()
                for m in v["models"]
            )
            return out

    # -- multi-tenant serving (ISSUE 6) ------------------------------------
    def attach_tenancy(self, mux) -> None:
        """Attach a TenantMux: /tenants/* routes go live, tenant-tagged
        queries flow through the weighted-fair scheduler and the model
        cache, and the mux's background sync (tenant refresh, rollout
        re-adoption, registry-driven prefetch) starts."""
        if self.dispatcher is None:
            log.warning(
                "tenancy attached with micro-batching disabled: "
                "weighted-fair scheduling is unavailable (quotas and "
                "the model cache still enforce)"
            )
        self.tenancy = mux
        mux.start()

    def tenant_weight(self, tenant_id: Optional[str]) -> float:
        """Fair-queue weight lookup the dispatcher calls per drain."""
        mux = self.tenancy
        return 1.0 if mux is None else mux.tenant_weight(tenant_id)

    def charge_device_seconds(self, tenant_id: str, seconds: float) -> None:
        """Dispatcher hook: post-paid device-time debit per tenant."""
        mux = self.tenancy
        if mux is not None:
            mux.charge_device_seconds(tenant_id, seconds)

    def bookkeep_variant(
        self, variant: str, seconds: float, error: bool
    ) -> None:
        self._variant_serve_hist.observe(seconds, variant=variant)
        self._variant_requests.inc(variant=variant)
        if error:
            self._variant_errors.inc(variant=variant)
        rollout = self.rollout
        if rollout is not None:
            rollout.record(variant, seconds, error)

    def maybe_shadow(
        self, raw: bytes, query_json: Any, result: Any,
        bucket: Optional[int] = None,
    ) -> None:
        """Shadow mode: mirror a fraction of live traffic to the
        candidate OFF the response path and score result agreement.
        The mirror runs the CANDIDATE's full serving path — its own
        query extraction and serving.supplement, not live's — so a
        candidate whose supplement/serializer is broken (or legitimately
        different) is judged on its own behavior. Bounded concurrency;
        mirror failures count as candidate errors."""
        from predictionio_tpu.deploy.rollout import sticky_candidate

        candidate, rollout = self.candidate, self.rollout
        if (
            candidate is None
            or rollout is None
            or not rollout.config.shadow
            or not sticky_candidate(
                raw, rollout.config.fraction, bucket=bucket
            )
            or not rollout.try_shadow()
        ):
            return

        def mirror():
            t0 = time.perf_counter()
            try:
                custom_from = getattr(
                    candidate.query_serializer, "query_from_json", None
                )
                if custom_from is not None:
                    query = custom_from(query_json)
                elif candidate.query_class is not None:
                    query = extract_params(candidate.query_class, query_json)
                else:
                    query = query_json
                supplemented = candidate.serving.supplement(query)
                if self.dispatcher is not None:
                    prediction = self.dispatcher.submit(
                        supplemented, candidate
                    )
                else:
                    predictions = [
                        algo.predict(model, supplemented)
                        for algo, model in zip(
                            candidate.algorithms, candidate.models
                        )
                    ]
                    prediction = candidate.serving.serve(
                        supplemented, predictions
                    )
                # serialize exactly as the live path does (custom
                # serializer included) so agreement compares like with
                # like — raw _to_jsonable vs a custom result_to_json
                # would read as 100% disagreement on such engines
                custom_to = getattr(
                    candidate.query_serializer, "result_to_json", None
                )
                shadow_result = (
                    custom_to(prediction) if custom_to is not None
                    else _to_jsonable(prediction)
                )
                rollout.record(
                    "candidate", time.perf_counter() - t0, error=False
                )
                rollout.record_agreement(shadow_result == result)
            except Exception:
                rollout.record(
                    "candidate", time.perf_counter() - t0, error=True
                )
                rollout.record_agreement(False)
            finally:
                rollout.shadow_done()

        rollout.run_shadow(mirror)

    def attach_rollout(self, controller, candidate: EngineRuntime) -> None:
        """Called by RolloutController.start() once the candidate runtime
        built successfully."""
        with self._swap_lock:
            if self.rollout is not None and self.rollout.st.state in (
                "starting", "canary"
            ):
                raise RolloutConflict(
                    f"rollout of {self.rollout.st.version.id} is already "
                    "active"
                )
            self.candidate = candidate
            self.rollout = controller

    def complete_rollout(self, controller, promote: bool) -> None:
        """Atomic end of a canary: promote hot-swaps candidate → live
        (the old runtime drains — in-flight queries keep their snapshot,
        zero dropped); rollback just detaches the candidate."""
        with self._swap_lock:
            if self.rollout is not controller:
                return  # stale controller (a newer rollout replaced it)
            if promote and self.candidate is not None:
                self.runtime = self.candidate
            self.candidate = None

    def start_rollout(self, body: dict) -> dict:
        """POST /rollout/start: canary a registered model version. With
        no explicit version, the newest `trained` version of the served
        engine variant is used."""
        from predictionio_tpu.deploy.registry import ModelRegistry
        from predictionio_tpu.deploy.rollout import (
            RolloutConfig,
            RolloutController,
        )

        registry = ModelRegistry(self.storage)
        vid = body.get("version")
        if vid:
            version = registry.get(vid)
            if version is None:
                raise ValueError(f"no model version {vid!r}")
        else:
            inst = self.runtime.instance
            trained = registry.list(
                inst.engine_id, inst.engine_variant, status="trained"
            )
            if not trained:
                raise ValueError(
                    f"no trained model version for {inst.engine_id}/"
                    f"{inst.engine_variant} — train (or `pio jobs submit`) "
                    "first"
                )
            version = trained[0]
        overrides = {
            k: body[k]
            for k in (
                "fraction", "window_s", "interval_s", "min_requests",
                "max_error_delta", "max_p99_ratio", "bake_s", "shadow",
                "min_agreement",
            )
            if k in body
        }
        config = RolloutConfig.from_env(**overrides)
        controller = RolloutController(self, version, config)
        try:
            controller.start()
        except (RolloutConflict, StorageError):
            # conflicts map to 409; a storage outage is the SERVER's
            # trouble (500), not a malformed request — automation that
            # treats 4xx as non-retryable must not be told 400 for it
            raise
        except Exception as e:
            # candidate build failed (model.load fault, missing blob):
            # the canary never started and live serving is untouched
            raise ValueError(f"canary start failed: {e}")
        return controller.status()

    def abort_rollout(self, reason: str) -> dict:
        rollout = self.rollout
        if rollout is None or rollout.st.state != "canary":
            raise RolloutConflict("no active rollout to abort")
        # stop the verdict thread FIRST, then re-check: the loop may
        # have promoted/rolled back between our check and the join — an
        # abort after that must not mark the now-live version rolled_back
        rollout.stop()
        if rollout.st.state != "canary":
            raise RolloutConflict(
                f"rollout already {rollout.st.state}; nothing to abort"
            )
        rollout.abort(reason)
        return rollout.status()

    def rollout_status(self) -> dict:
        rollout = self.rollout
        if rollout is None:
            return {"state": "none"}
        return rollout.status()

    # -- bookkeeping (registry-backed; the averages are now derived) -------
    def bookkeep(self, seconds: float) -> None:
        self.last_serving_sec = seconds
        self._serve_hist.observe(seconds)

    def bookkeep_predict(self, seconds: float, batch_size: int) -> None:
        """Device-side (model compute incl. result fetch) time per query,
        isolated from HTTP/queue overhead so end-to-end numbers don't
        mask device latency."""
        per_query = seconds / max(1, batch_size)
        self.last_predict_sec = per_query
        self._predict_hist.observe(per_query)

    @property
    def request_count(self) -> int:
        return self._serve_hist.count

    @property
    def avg_serving_sec(self) -> float:
        return self._serve_hist.mean

    @property
    def predict_count(self) -> int:
        return self._predict_hist.count

    @property
    def avg_predict_sec(self) -> float:
        return self._predict_hist.mean

    # -- feedback loop (reference CreateServer.scala:534-596) --------------
    def feedback_async(self, query_json: dict, result: Any) -> None:
        if not self.config.feedback:
            return
        if not (self.config.event_server_url and self.config.access_key):
            log.warning("feedback enabled but event server url/key missing")
            return

        def post():
            try:
                pr_id = (
                    result.get("pr_id")
                    if isinstance(result, dict) and result.get("pr_id")
                    else self.runtime.instance.id
                )
                event = {
                    "event": "predict",
                    "entityType": "pio_pr",
                    "entityId": pr_id,
                    "properties": {"query": query_json, "prediction": result},
                    "prId": pr_id,
                }
                url = (
                    f"{self.config.event_server_url}/events.json"
                    f"?accessKey={self.config.access_key}"
                )
                req = urllib.request.Request(
                    url,
                    data=json.dumps(event).encode(),
                    headers={"Content-Type": "application/json"},
                    method="POST",
                )
                urllib.request.urlopen(req, timeout=10).read()
            except Exception:
                log.exception("feedback event POST failed")
            finally:
                with self._feedback_lock:
                    self._feedback_threads.discard(
                        threading.current_thread()
                    )

        t = threading.Thread(target=post, name="feedback-post", daemon=True)
        with self._feedback_lock:
            self._feedback_threads.add(t)
        t.start()

    # -- status page (reference CreateServer.scala:461-489 Twirl html) -----
    def status_html(self) -> str:
        """Rendered FROM the metrics registry (averages + p50/p95/p99
        come from the serve/predict histograms). All engine/instance
        fields and params reprs are escaped — they carry user-authored
        strings (engine.json), same as tools/dashboard.py already did."""
        esc = _html.escape
        rt = self.runtime
        inst = rt.instance
        serve, predict = self._serve_hist, self._predict_hist
        count = serve.count
        avg, avg_p = serve.mean, predict.mean
        last, last_p = self.last_serving_sec, self.last_predict_sec
        q = lambda h, p: h.quantile(p) * 1000.0  # noqa: E731
        window_ms = (
            self.dispatcher.window_s * 1000.0 if self.dispatcher else 0.0
        )
        algo_rows = "".join(
            f"<tr><td>{esc(type(a).__name__)}</td><td>{esc(name)}</td>"
            f"<td><code>{esc(repr(params))}</code></td></tr>"
            for a, (name, params) in zip(
                rt.algorithms, rt.engine_params.algorithm_params_list
            )
        )
        return f"""<!DOCTYPE html><html><head><title>{esc(inst.engine_id)} — predictionio_tpu</title></head>
<body>
<h1>Engine {esc(inst.engine_id)} ({esc(inst.engine_variant)})</h1>
<table>
<tr><td>Instance</td><td>{esc(inst.id)}</td></tr>
<tr><td>Factory</td><td>{esc(inst.engine_factory)}</td></tr>
<tr><td>Trained</td><td>{esc(str(inst.end_time))}</td></tr>
<tr><td>Serving since</td><td>{esc(str(rt.started_at))}</td></tr>
<tr><td>Requests</td><td>{count}</td></tr>
<tr><td>Average serve time</td><td>{avg * 1000:.3f} ms</td></tr>
<tr><td>Serve p50 / p95 / p99</td><td>{q(serve, 0.5):.3f} / {q(serve, 0.95):.3f} / {q(serve, 0.99):.3f} ms</td></tr>
<tr><td>Last serve time</td><td>{last * 1000:.3f} ms</td></tr>
<tr><td>Average device predict time</td><td>{avg_p * 1000:.3f} ms</td></tr>
<tr><td>Predict p50 / p95 / p99</td><td>{q(predict, 0.5):.3f} / {q(predict, 0.95):.3f} / {q(predict, 0.99):.3f} ms</td></tr>
<tr><td>Last device predict time</td><td>{last_p * 1000:.3f} ms</td></tr>
<tr><td>Serve − predict = HTTP/queue/transport overhead</td><td>{(avg - avg_p) * 1000:.3f} ms</td></tr>
<tr><td>Micro-batch window (adaptive)</td><td>{window_ms:.2f} ms</td></tr>
</table>
<h2>Algorithms</h2>
<table><tr><th>class</th><th>name</th><th>params</th></tr>{algo_rows}</table>
<p><a href="/reload">reload model</a> · <a href="/metrics">prometheus metrics</a></p>
</body></html>"""
