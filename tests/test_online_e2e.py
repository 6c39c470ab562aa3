"""Online learning end-to-end (ISSUE 9 acceptance): a brand-new user's
events fold into the LIVE serving model and `recommend` personalizes
without a retrain; a consumer killed mid-tick resumes from its durable
cursor with no lost and no double-applied events; injected drift pauses
fold-in, fires an alert, and leaves the last-good model serving."""

import json
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from predictionio_tpu.data.event import Event
from predictionio_tpu.data.storage.base import App
from predictionio_tpu.online import (
    OnlineConsumer,
    OnlineConsumerConfig,
    ServerApplyHost,
)
from predictionio_tpu.resilience import faults
from predictionio_tpu.workflow.core import run_train
from predictionio_tpu.workflow.server import (
    QueryServer,
    QueryServerConfig,
    build_runtime,
)

VARIANT = {
    "id": "onl",
    "engineFactory":
        "predictionio_tpu.engines.recommendation.RecommendationEngine",
    "datasource": {"params": {"app_name": "onlapp"}},
    "algorithms": [
        {"name": "als", "params": {"rank": 8, "num_iterations": 4}}
    ],
}

# two disjoint taste clusters: even users rate items 0-4, odd users 5-9
N_SEED_EVENTS_PER_USER = 20


def _seed(storage, n_users=8, seed=0):
    apps = storage.get_meta_data_apps()
    app_id = apps.insert(App(id=0, name="onlapp"))
    events = storage.get_events()
    events.init_app(app_id)
    rng = np.random.RandomState(seed)
    batch = []
    for u in range(n_users):
        for _ in range(N_SEED_EVENTS_PER_USER):
            i = rng.randint(0, 5) + (u % 2) * 5
            batch.append(Event(
                event="rate", entity_type="user", entity_id=f"u{u}",
                target_entity_type="item", target_entity_id=f"i{i}",
                properties={"rating": 5.0},
            ))
    events.insert_batch(batch, app_id)
    return app_id


def _post(port, path, body, timeout=30):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"},
        method="POST",
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read().decode())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read().decode() or "null")


def _get(port, path):
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=30
    ) as r:
        return r.status, json.loads(r.read().decode())


@pytest.fixture()
def served(fresh_storage):
    """A live query server over a trained model, no consumer yet."""
    app_id = _seed(fresh_storage)
    inst = run_train(fresh_storage, VARIANT)
    runtime = build_runtime(fresh_storage, inst)
    srv = QueryServer(
        fresh_storage, runtime,
        QueryServerConfig(ip="127.0.0.1", port=0, batch_window_ms=1.0),
    )
    port = srv.start()
    yield fresh_storage, srv, port, app_id
    faults.clear()
    srv.stop()


def _rate(uid, items, rating=5.0):
    return [
        Event(
            event="rate", entity_type="user", entity_id=uid,
            target_entity_type="item", target_entity_id=i,
            properties={"rating": rating},
        )
        for i in items
    ]


class TestColdStartFoldIn:
    def test_new_user_personalized_without_retrain(self, served):
        """The headline acceptance: a brand-new user's events stream in
        AFTER the model trained; the running consumer folds them and
        `recommend` answers personalized (non-empty, cluster-matching)
        results — with no retrain and zero serving interruption."""
        storage, srv, port, app_id = served
        tick_s = 0.1
        srv.attach_online(
            app_id,
            OnlineConsumerConfig(tick_s=tick_s, from_latest=True),
        )
        # an unknown user gets the empty (popularity-fallback-free)
        # result — the "before" picture
        status, body = _post(
            port, "/queries.json", {"user": "newbie", "num": 5}
        )
        assert status == 200 and body["item_scores"] == []

        storage.get_events().insert_batch(
            _rate("newbie", ["i5", "i6", "i7"]), app_id
        )
        t0 = time.perf_counter()
        deadline = time.monotonic() + 30.0
        scores = []
        while time.monotonic() < deadline:
            status, body = _post(
                port, "/queries.json", {"user": "newbie", "num": 5}
            )
            assert status == 200
            if body["item_scores"]:
                scores = body["item_scores"]
                break
            time.sleep(0.02)
        visible_after = time.perf_counter() - t0
        assert scores, "new user never became visible to serving"
        # personalized, not popularity: the top items come from the
        # odd-user cluster (i5..i9) this user's ratings match
        top = {s["item"] for s in scores[:3]}
        assert top <= {f"i{j}" for j in range(5, 10)}, scores
        # visibility latency is tick-bounded (generous CI slack; the
        # tight < 2-tick bar is not measured anywhere)
        assert visible_after < 30.0
        st = _get(port, "/online/status")[1]
        assert st["state"] == "attached"
        assert st["counters"]["events_folded"] >= 3
        assert st["counters"]["users_folded"] >= 1

    def test_new_item_folds_symmetrically(self, served):
        storage, srv, port, app_id = served
        consumer = srv.attach_online(
            app_id, OnlineConsumerConfig(tick_s=60, from_latest=True),
        )
        consumer.stop()  # drive ticks manually
        # three odd-cluster users rate a brand-new item
        storage.get_events().insert_batch(
            [e for u in ("u1", "u3", "u5") for e in _rate(u, ["fresh"])],
            app_id,
        )
        out = consumer.tick()
        assert out["stats"]["items_added"] == 1
        assert out["stats"]["items_folded"] == 1
        # the new item is servable: similar odd-cluster users see it
        # scored (it shares their taste vector)
        ix, model = consumer.foldin.find_model(srv.runtime)
        assert model.factors.item_vocab.get("fresh") is not None
        row = model.factors.item_vocab("fresh")
        assert np.abs(model.factors.item_factors[row]).sum() > 0

    def test_new_item_overflow_carries_to_next_tick(self, served):
        """New items beyond max_items_per_tick must not be stranded with
        zero factor rows: the overflow solves on the following ticks."""
        import dataclasses as _dc

        storage, srv, port, app_id = served
        consumer = srv.attach_online(
            app_id, OnlineConsumerConfig(tick_s=60, from_latest=True),
        )
        consumer.stop()
        consumer.foldin.config = _dc.replace(
            consumer.foldin.config, max_items_per_tick=2
        )
        # an EXISTING user (nonzero factors — a brand-new user rating
        # only brand-new items is mutually zero-signal for single-pass
        # fold-in) rates 5 brand-new items in one tick
        storage.get_events().insert_batch(
            _rate("u1", [f"bulk{j}" for j in range(5)]), app_id
        )
        out = consumer.tick()
        assert out["stats"]["items_added"] == 5
        assert out["stats"]["items_folded"] == 2
        # a tick of IRRELEVANT traffic must also drain the carry (not
        # just a fully idle stream)
        storage.get_events().insert(
            Event(event="$set", entity_type="user", entity_id="u1",
                  properties={"plan": "pro"}),
            app_id,
        )
        out = consumer.tick()
        folded = 2 + out["stats"]["items_folded"]
        # the stream goes QUIET: idle ticks drain the rest
        for k in range(3):
            out = consumer.tick()
            if "stats" in out and out["stats"]:
                folded += out["stats"]["items_folded"]
        assert folded == 5
        assert consumer.foldin.pending_items == []
        assert consumer.tick() == {"idle": "no new events"}
        _ix, model = consumer.foldin.find_model(srv.runtime)
        for j in range(5):
            row = model.factors.item_vocab(f"bulk{j}")
            assert np.abs(model.factors.item_factors[row]).sum() > 0, (
                f"bulk{j} left with a zero factor row"
            )

    def test_discarded_tick_keeps_item_carry(self, served):
        """A discarded fold result (here: a lost swap race — a retrain
        promoting mid-tick; same path as a drift breach) must not
        consume the carried item-solve list — the commit happens only
        on a successful publish."""
        import dataclasses as _dc

        storage, srv, port, app_id = served
        consumer = srv.attach_online(
            app_id, OnlineConsumerConfig(tick_s=60, from_latest=True),
        )
        consumer.stop()
        consumer.foldin.config = _dc.replace(
            consumer.foldin.config, max_items_per_tick=2
        )
        storage.get_events().insert_batch(
            _rate("u1", [f"held{j}" for j in range(4)]), app_id
        )
        assert consumer.tick()["stats"]["items_folded"] == 2
        pending_before = consumer.foldin.pending_items
        assert len(pending_before) == 2
        # the drain tick loses the publish race → result discarded
        host = consumer.host
        orig_swap = host.swap
        host.swap = lambda old, new: False
        out = consumer.tick()
        assert out == {"retry": "runtime changed during fold"}
        assert consumer.foldin.pending_items == pending_before
        host.swap = orig_swap
        out = consumer.tick()  # clean drain publishes and commits
        assert out["stats"]["items_folded"] == 2
        assert consumer.foldin.pending_items == []

    def test_online_pause_resume_endpoints(self, served):
        storage, srv, port, app_id = served
        srv.attach_online(
            app_id, OnlineConsumerConfig(tick_s=60, from_latest=True),
        )
        status, st = _post(port, "/online/pause", {"reason": "ops"})
        assert status == 200 and st["paused"] == "ops"
        status, st = _post(port, "/online/resume", {})
        assert status == 200 and st["paused"] is None
        # detached server answers 404 on pause and "detached" on status
        srv.online.stop()
        srv.online = None
        assert _post(port, "/online/pause", {})[0] == 404
        assert _get(port, "/online/status")[1]["state"] == "detached"


class TestCursorCrashResume:
    def test_killed_mid_tick_no_loss_no_double_apply(self, served):
        """Chaos acceptance: the consumer dies BETWEEN applying a fold
        and persisting its cursor — the worst-case window. A fresh
        consumer resumes from the durable cursor; the fold counters
        show every relevant event applied exactly once."""
        storage, srv, port, app_id = served
        cfg = OnlineConsumerConfig(tick_s=60, from_latest=True)
        c1 = OnlineConsumer(
            storage, ServerApplyHost(srv), app_id, cfg,
        )
        # phase 1: a clean tick lands and persists
        storage.get_events().insert_batch(
            _rate("crash-a", ["i5", "i6"]), app_id
        )
        out = c1.tick()
        assert out["folded"] == 2
        # phase 2: crash mid-tick, AFTER the runtime swap
        storage.get_events().insert_batch(
            _rate("crash-b", ["i7", "i8", "i9"]), app_id
        )
        c1._crash_after_apply = True
        with pytest.raises(RuntimeError):
            c1.tick()
        # the fold DID reach serving...
        status, body = _post(
            port, "/queries.json", {"user": "crash-b", "num": 3}
        )
        assert status == 200 and body["item_scores"]
        # ...but was never accounted: the durable record still says 2
        c2 = OnlineConsumer(
            storage, ServerApplyHost(srv), app_id, cfg,
        )
        assert c2.counters["events_folded"] == 2
        out = c2.tick()  # replays the un-persisted window
        assert out["folded"] == 3
        # exactly-once accounting: 5 relevant events inserted → folded
        # counter says exactly 5, not 2 (lost) and not 8 (double)
        assert c2.counters["events_folded"] == 5
        assert c2.counters["events_consumed"] == 5
        assert c2.tick() == {"idle": "no new events"}
        assert c2.counters["events_folded"] == 5
        # the replayed fold is idempotent in model state: crash-b still
        # answers, and from the same history
        status, body = _post(
            port, "/queries.json", {"user": "crash-b", "num": 3}
        )
        assert status == 200 and body["item_scores"]


class TestDriftGuard:
    def test_injected_drift_pauses_alerts_and_serves_last_good(
        self, served
    ):
        """Chaos acceptance: a corrupting fault on the fold solve drives
        score drift past the threshold → fold-in pauses, a monitor
        alert fires, the cursor freezes, and serving keeps answering
        from the last-good model. Clearing the fault and resuming
        re-folds the same window cleanly."""
        from predictionio_tpu.obs.monitor import get_monitor

        storage, srv, port, app_id = served
        consumer = srv.attach_online(
            app_id,
            OnlineConsumerConfig(
                tick_s=60, from_latest=True, drift_threshold=0.5,
            ),
        )
        consumer.stop()  # manual ticks
        baseline_runtime = srv.runtime
        _status, before = _post(
            port, "/queries.json", {"user": "u1", "num": 3}
        )

        # every existing user re-rates → every user row re-solves, all
        # of them corrupted by the injected fault
        storage.get_events().insert_batch(
            [e for u in range(8) for e in _rate(f"u{u}", ["i2"], 3.0)],
            app_id,
        )
        faults.install(faults.FaultSpec("online.fold", "corrupt", 1.0))
        out = consumer.tick()
        assert "paused" in out and out["drift"] > 0.5
        assert consumer.paused
        # last-good model serves: the runtime reference never moved and
        # answers are unchanged
        assert srv.runtime is baseline_runtime
        _status, after = _post(
            port, "/queries.json", {"user": "u1", "num": 3}
        )
        assert after == before
        # the cursor did NOT advance (nothing lost)
        assert consumer.counters["events_consumed"] == 0
        # the alert is pio-alerts visible and firing, under a
        # per-consumer name (two scopes must not share one alert)
        payload = get_monitor().alerts_payload()
        assert consumer.alert_name in payload["firing"]
        assert consumer.alert_name.endswith(consumer.cursor_id)
        st = _get(port, "/online/status")[1]
        assert st["paused"]

        # recovery: clear the fault, resume, re-fold the window cleanly
        faults.clear()
        consumer.resume()
        out = consumer.tick()
        assert out.get("folded") == 8
        assert consumer.paused is None
        assert srv.runtime is not baseline_runtime
        assert (
            consumer.alert_name
            not in get_monitor().alerts_payload()["firing"]
        )

    def test_retrain_auto_resumes_drift_pause(self, served):
        """The alert's other documented recovery path: a retrain landing
        while DRIFT-paused rebases the baseline and resumes fold-in
        without an explicit /online/resume (operator pauses stay)."""
        from predictionio_tpu.obs.monitor import get_monitor

        storage, srv, port, app_id = served
        consumer = srv.attach_online(
            app_id,
            OnlineConsumerConfig(
                tick_s=60, from_latest=True, drift_threshold=0.5,
            ),
        )
        consumer.stop()
        storage.get_events().insert_batch(
            [e for u in range(8) for e in _rate(f"u{u}", ["i2"], 3.0)],
            app_id,
        )
        faults.install(faults.FaultSpec("online.fold", "corrupt", 1.0))
        assert "paused" in consumer.tick()
        faults.clear()
        # a retrain lands and is reloaded — no explicit resume
        run_train(storage, VARIANT)
        srv.reload()
        out = consumer.tick()
        assert consumer.paused is None
        assert out.get("folded") == 8
        assert (
            consumer.alert_name
            not in get_monitor().alerts_payload()["firing"]
        )
        # an OPERATOR pause does NOT auto-clear on retrain
        consumer.pause("operator hold")
        run_train(storage, VARIANT)
        srv.reload()
        assert consumer.tick() == {"paused": "operator hold"}

    def test_drift_cooldown_delays_resume_after_retrain(self, served):
        """ISSUE 19 satellite: with PIO_ONLINE_DRIFT_COOLDOWN_S (here
        via config) a drift-paused consumer does NOT resume the moment a
        retrain lands — it waits out the cool-down, then the next tick
        re-probes drift by folding and stays resumed when clean."""
        storage, srv, port, app_id = served
        consumer = srv.attach_online(
            app_id,
            OnlineConsumerConfig(
                tick_s=60, from_latest=True, drift_threshold=0.5,
                drift_cooldown_s=0.4,
            ),
        )
        consumer.stop()
        storage.get_events().insert_batch(
            [e for u in range(8) for e in _rate(f"u{u}", ["i2"], 3.0)],
            app_id,
        )
        faults.install(faults.FaultSpec("online.fold", "corrupt", 1.0))
        assert "paused" in consumer.tick()
        faults.clear()
        run_train(storage, VARIANT)
        srv.reload()
        # the retrain alone no longer resumes: this tick sees the new
        # runtime, rebases, and starts the cool-down clock
        out = consumer.tick()
        assert "paused" in out
        assert consumer.status()["cooling_down"] is True
        assert consumer.paused is not None
        # ... and once the cool-down expires, the next tick resumes and
        # the fold itself is the drift re-probe
        time.sleep(0.45)
        out = consumer.tick()
        assert consumer.paused is None
        assert out.get("folded") == 8
        assert consumer.status()["cooling_down"] is False
        # an OPERATOR pause never auto-resumes, cool-down or not
        consumer.pause("operator hold")
        run_train(storage, VARIANT)
        srv.reload()
        time.sleep(0.45)
        assert consumer.tick() == {"paused": "operator hold"}

    def test_error_fault_fails_tick_without_cursor_advance(self, served):
        storage, srv, port, app_id = served
        consumer = srv.attach_online(
            app_id, OnlineConsumerConfig(tick_s=60, from_latest=True),
        )
        consumer.stop()
        storage.get_events().insert_batch(_rate("ef", ["i1"]), app_id)
        faults.install(faults.FaultSpec("online.fold", "error", 1.0))
        with pytest.raises(faults.FaultInjected):
            consumer.tick()
        assert consumer.counters["events_consumed"] == 0
        faults.clear()
        assert consumer.tick()["folded"] == 1


class TestControlPlane:
    def test_admin_online_view_and_dashboard_panel(self, served):
        from predictionio_tpu.tools.admin import AdminServer

        storage, srv, port, app_id = served
        consumer = srv.attach_online(
            app_id, OnlineConsumerConfig(tick_s=60, from_latest=True),
        )
        consumer.stop()
        storage.get_events().insert_batch(_rate("adm", ["i5"]), app_id)
        consumer.tick()
        admin = AdminServer(storage, ip="127.0.0.1", port=0)
        admin_port = admin.start()
        try:
            status, body = _get(admin_port, "/online")
            assert status == 200
            rows = body["consumers"]
            assert len(rows) == 1
            assert rows[0]["cursor_id"] == consumer.cursor_id
            assert rows[0]["events_folded"] == 1
        finally:
            admin.stop()

    def test_same_version_rebuild_refolds_overlay(self, served):
        """A runtime rebuilt from the SAME trained instance (operator
        /reload, cache eviction) discards the fold overlay — the cursor
        rewinds to the baseline watermark and the window re-folds, so a
        folded cold-start user survives the rebuild."""
        storage, srv, port, app_id = served
        consumer = srv.attach_online(
            app_id, OnlineConsumerConfig(tick_s=60, from_latest=True),
        )
        consumer.stop()
        storage.get_events().insert_batch(
            _rate("phoenix", ["i5", "i6"]), app_id
        )
        assert consumer.tick()["folded"] == 2
        status, body = _post(
            port, "/queries.json", {"user": "phoenix", "num": 3}
        )
        assert body["item_scores"]
        # rebuild from the SAME version: the overlay is gone...
        srv.reload()
        status, body = _post(
            port, "/queries.json", {"user": "phoenix", "num": 3}
        )
        assert body["item_scores"] == []
        # ...until the next tick rewinds and re-folds it
        out = consumer.tick()
        assert out["folded"] == 2
        status, body = _post(
            port, "/queries.json", {"user": "phoenix", "num": 3}
        )
        assert body["item_scores"]

    def test_retrain_rebases_drift_baseline(self, served):
        """A retrain swapping the runtime mid-stream becomes the new
        drift baseline; folding continues on top of it."""
        storage, srv, port, app_id = served
        consumer = srv.attach_online(
            app_id, OnlineConsumerConfig(tick_s=60, from_latest=True),
        )
        consumer.stop()
        storage.get_events().insert_batch(_rate("rb", ["i5"]), app_id)
        assert consumer.tick()["folded"] == 1
        old_baseline = consumer.guard._baseline
        # a retrain lands and the operator reloads
        run_train(storage, VARIANT)
        srv.reload()
        storage.get_events().insert_batch(_rate("rb2", ["i6"]), app_id)
        out = consumer.tick()
        assert out["folded"] == 1
        assert consumer.guard._baseline is not old_baseline
        # the fresh model serves the folded user
        status, body = _post(
            port, "/queries.json", {"user": "rb2", "num": 3}
        )
        assert status == 200 and body["item_scores"]
