"""Deploy-server HTTP tests: query serving, hot reload, feedback loop."""

import json
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from predictionio_tpu.data.api.server import EventServer, EventServerConfig
from predictionio_tpu.data.event import Event
from predictionio_tpu.data.storage.base import AccessKey, App
from predictionio_tpu.workflow.core import run_train
from predictionio_tpu.workflow.server import (
    QueryServer,
    QueryServerConfig,
    latest_completed_runtime,
)

VARIANT = {
    "id": "qsrv",
    "engineFactory": "predictionio_tpu.engines.recommendation.RecommendationEngine",
    "datasource": {"params": {"app_name": "qapp"}},
    "algorithms": [
        {"name": "als", "params": {"rank": 8, "num_iterations": 6}}
    ],
}


def seed(storage, n_users=8, seed=0):
    apps = storage.get_meta_data_apps()
    app = apps.get_by_name("qapp")
    app_id = app.id if app else apps.insert(App(id=0, name="qapp"))
    events = storage.get_events()
    events.init_app(app_id)
    rng = np.random.RandomState(seed)
    batch = []
    for u in range(n_users):
        for _ in range(20):
            i = rng.randint(0, 5) + (u % 2) * 5
            batch.append(
                Event(
                    event="rate", entity_type="user", entity_id=f"u{u}",
                    target_entity_type="item", target_entity_id=f"i{i}",
                    properties={"rating": 5.0},
                )
            )
    events.insert_batch(batch, app_id)
    return app_id


def post(port, path, body):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"},
        method="POST",
    )
    try:
        with urllib.request.urlopen(req, timeout=15) as r:
            return r.status, json.loads(r.read().decode())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read().decode() or "null")


def get(port, path):
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=15
    ) as r:
        return r.status, r.read().decode()


@pytest.fixture()
def served(fresh_storage):
    seed(fresh_storage)
    run_train(fresh_storage, VARIANT)
    runtime = latest_completed_runtime(fresh_storage, "qsrv", "0", "qsrv")
    srv = QueryServer(
        fresh_storage, runtime, QueryServerConfig(ip="127.0.0.1", port=0)
    )
    port = srv.start()
    yield fresh_storage, srv, port
    srv.stop()


def test_queries(served):
    _, srv, port = served
    status, body = post(port, "/queries.json", {"user": "u0", "num": 3})
    assert status == 200
    assert len(body["item_scores"]) == 3
    items = {s["item"] for s in body["item_scores"]}
    assert items <= {f"i{i}" for i in range(5)}  # cohort-0 items

    # unknown user → 200 with empty result (graceful)
    status, body = post(port, "/queries.json", {"user": "ghost"})
    assert status == 200 and body["item_scores"] == []


def test_query_validation(served):
    _, _, port = served
    status, body = post(port, "/queries.json", {"user": "u0", "bogus": 1})
    assert status == 400
    assert "unknown params" in body["message"]

    status, body = post(port, "/queries.json", [1, 2])
    assert status == 400

    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/queries.json", data=b"{nope",
        headers={"Content-Type": "application/json"}, method="POST",
    )
    with pytest.raises(urllib.error.HTTPError) as ei:
        urllib.request.urlopen(req, timeout=15)
    assert ei.value.code == 400


def test_status_page_and_bookkeeping(served):
    _, srv, port = served
    post(port, "/queries.json", {"user": "u0"})
    post(port, "/queries.json", {"user": "u1"})
    status, html = get(port, "/")
    assert status == 200
    assert "qsrv" in html and "Requests" in html
    assert srv.request_count == 2
    assert srv.avg_serving_sec > 0


def test_hot_reload_swaps_to_latest(served):
    storage, srv, port = served
    first_id = srv.runtime.instance.id
    # new data + retrain → new COMPLETED instance
    seed(storage, seed=1)
    run_train(storage, VARIANT)
    status, body = get(port, "/reload")
    assert status == 200
    assert srv.runtime.instance.id != first_id
    status, body = post(port, "/queries.json", {"user": "u0", "num": 2})
    assert status == 200 and len(body["item_scores"]) == 2


def test_micro_batching(fresh_storage):
    """Concurrent queries coalesce into batched device calls and still get
    the right per-user answers."""
    import concurrent.futures

    seed(fresh_storage)
    run_train(fresh_storage, VARIANT)
    runtime = latest_completed_runtime(fresh_storage, "qsrv", "0", "qsrv")
    srv = QueryServer(
        fresh_storage,
        runtime,
        QueryServerConfig(
            ip="127.0.0.1", port=0, micro_batch=True, batch_window_ms=10.0
        ),
    )
    port = srv.start()
    try:
        with concurrent.futures.ThreadPoolExecutor(8) as pool:
            futures = {
                u: pool.submit(post, port, "/queries.json", {"user": f"u{u}", "num": 3})
                for u in range(8)
            }
            results = {u: f.result() for u, f in futures.items()}
        for u, (status, body) in results.items():
            assert status == 200
            items = {s["item"] for s in body["item_scores"]}
            lo, hi = (0, 5) if u % 2 == 0 else (5, 10)
            cohort = {f"i{i}" for i in range(lo, hi)}
            assert items <= cohort, (u, items)
        # validation still 400s through the batched path
        status, body = post(port, "/queries.json", {"user": "u0", "oops": 1})
        assert status == 400
    finally:
        srv.stop()


def test_default_config_batches(served):
    """Micro-batching is ON by default (VERDICT r1 #6: the measured fast
    path must be the default path)."""
    _, srv, _ = served
    assert srv.dispatcher is not None
    assert srv.config.micro_batch


def test_debug_traces_stats_answers_for_the_asked_window(served, capsys):
    """ISSUE 25: where the last seconds went, by span name, from the
    spans themselves — every query, not only the sampled traces."""
    _, _, port = served
    for u in range(4):
        status, _ = post(port, "/queries.json", {"user": f"u{u}", "num": 3})
        assert status == 200
    # the root span records as the reply goes out: poll for the fourth
    deadline = time.time() + 10
    while time.time() < deadline:
        _, text = get(port, "/debug/traces?stats=1&window=30")
        table = json.loads(text)
        if table["spans"].get("server.request", {}).get("count", 0) >= 4:
            break
        time.sleep(0.05)
    assert table["window_s"] == 30.0
    spans = table["spans"]
    for name in ("server.request", "query.decode", "query.wait",
                 "query.encode", "batch.queue_wait", "batch.predict",
                 "batch.serve", "als.predict.prepare", "als.predict.device",
                 "als.predict.decode"):
        assert spans[name]["count"] >= 1, name
        assert 0.0 <= spans[name]["self_s"] <= spans[name]["total_s"] + 1e-9
    assert spans["query.wait"]["count"] >= 4
    # a request's self time leaves out what its child spans cover
    req = spans["server.request"]
    assert req["self_s"] < req["total_s"]
    # ISSUE 26: the decode span has no child since inverse() stopped
    # copying the vocabulary, so all of it is its own
    assert "als.predict.vocab_inverse" not in spans
    decode = spans["als.predict.decode"]
    assert decode["self_s"] == pytest.approx(decode["total_s"])
    # a window that holds nothing yet answers with an empty table
    _, text = get(port, "/debug/traces?stats=1&window=bogus")
    assert json.loads(text)["window_s"] == 60.0

    # `pio trace stats` prints the same table
    from predictionio_tpu.tools import console

    assert console.main(
        ["trace", "stats", "--url", f"http://127.0.0.1:{port}",
         "--window", "30"]) == 0
    out = capsys.readouterr().out
    assert "als.predict.decode" in out and "self_s" in out


def test_load_32_clients_qps_and_p99(served):
    """32 concurrent clients against the DEFAULT config, as behaviour
    (how many queries a second, and what tail, is the chip benchmark's to
    say): every request answers, the dispatcher coalesces concurrent
    arrivals into batches of more than one query, and the device-time
    bookkeeping moves."""
    import concurrent.futures

    _, srv, port = served
    n_clients, n_per = 32, 8

    def client(u):
        return [
            post(port, "/queries.json", {"user": f"u{u % 8}", "num": 3})
            for _ in range(n_per)
        ]

    with concurrent.futures.ThreadPoolExecutor(n_clients) as pool:
        replies = [r for rs in pool.map(client, range(n_clients)) for r in rs]
    assert len(replies) == n_clients * n_per
    assert all(status == 200 for status, _ in replies)
    assert all(len(body["item_scores"]) == 3 for _, body in replies)
    batch_size = next(
        f for f in srv.metrics.families() if f.name == "batch_size"
    )
    assert batch_size.sum == n_clients * n_per  # every query rode a batch
    assert batch_size.count < batch_size.sum  # some batch held several
    # device-side latency is bookkept separately from end-to-end
    assert srv.predict_count > 0
    assert srv.avg_predict_sec <= srv.avg_serving_sec


def test_feedback_loop(fresh_storage):
    app_id = seed(fresh_storage)
    fresh_storage.get_meta_data_access_keys().insert(
        AccessKey(key="FB", app_id=app_id, events=())
    )
    es = EventServer(
        fresh_storage, EventServerConfig(ip="127.0.0.1", port=0)
    )
    es_port = es.start()
    run_train(fresh_storage, VARIANT)
    runtime = latest_completed_runtime(fresh_storage, "qsrv", "0", "qsrv")
    srv = QueryServer(
        fresh_storage,
        runtime,
        QueryServerConfig(
            ip="127.0.0.1",
            port=0,
            feedback=True,
            event_server_url=f"http://127.0.0.1:{es_port}",
            access_key="FB",
        ),
    )
    port = srv.start()
    try:
        status, _ = post(port, "/queries.json", {"user": "u0"})
        assert status == 200
        deadline = time.time() + 10
        found = []
        while time.time() < deadline and not found:
            found = list(
                fresh_storage.get_events().find_single_entity(
                    app_id, "pio_pr", runtime.instance.id,
                    event_names=["predict"],
                )
            )
            time.sleep(0.1)
        assert found, "feedback predict event never arrived"
        props = found[0].properties
        assert props.get_opt("query", dict) == {"user": "u0"}
    finally:
        srv.stop()
        es.stop()


def test_dispatcher_coalesces_under_device_occupancy():
    """Drain-until-idle policy (VERDICT r3 #3): while one batch occupies
    the (request-serialized) device path, concurrent arrivals coalesce
    into ONE next batch instead of fragmenting into per-query dispatches."""
    import time as _t
    from concurrent.futures import ThreadPoolExecutor

    from predictionio_tpu.workflow.server import _BatchDispatcher

    batch_sizes = []

    class _SlowAlgo:
        serving_context = None

        def batch_predict(self, ctx, model, queries):
            batch_sizes.append(len(queries))
            _t.sleep(0.05)  # the "device" is busy for 50 ms
            return [(qx, f"p{qx}") for qx, _q in queries]

    class _Serving:
        def serve(self, q, preds):
            return preds[0]

    class _Owner:
        def bookkeep_predict(self, *_a):
            pass

    class _RT:
        algorithms = [_SlowAlgo()]
        models = [None]
        serving = _Serving()

    rt = _RT()
    disp = _BatchDispatcher(
        _Owner(), window_ms=2.0, max_batch=64, max_window_ms=60.0,
        pipeline_depth=4,
    )
    try:
        disp.submit("warm", rt)  # first dispatch; occupies the device
        batch_sizes.clear()

        def client(i):
            # stagger arrivals over ~15 ms — all inside the first
            # in-flight batch's 50 ms occupancy window
            _t.sleep(0.001 * (i % 15))
            return disp.submit(f"q{i}", rt)

        with ThreadPoolExecutor(24) as pool:
            results = list(pool.map(client, range(24)))
        assert len(results) == 24
        # 24 staggered queries must NOT become 24 dispatches; the policy
        # coalesces what arrives behind an in-flight batch. Bounds are
        # generous (≤12 fragments, one batch ≥4) so a CPU-starved CI
        # host that stretches the arrival stagger doesn't flake this.
        assert sum(batch_sizes) == 24
        assert len(batch_sizes) <= 12, f"fragmented into {batch_sizes}"
        assert max(batch_sizes) >= 4, f"no deep batch formed: {batch_sizes}"
    finally:
        disp.stop()
