"""CCO kernel + Universal Recommender engine tests."""

import numpy as np
import pytest

from predictionio_tpu.core.base import RuntimeContext
from predictionio_tpu.data.event import Event
from predictionio_tpu.data.storage.base import App
from predictionio_tpu.models import cco
from predictionio_tpu.workflow.core import prepare_deploy_models, run_train


class TestCCOKernel:
    def test_counts_and_llr_shape(self):
        # users 0-3 buy item 0 AND view thing 1 → strong correlation
        primary = cco.edges_to_indicator(
            np.array([0, 1, 2, 3, 4, 5]), np.array([0, 0, 0, 0, 1, 1]), 6, 2
        )
        secondary = cco.edges_to_indicator(
            np.array([0, 1, 2, 3, 4, 5]), np.array([1, 1, 1, 1, 0, 0]), 6, 2
        )
        scores, idx = cco.cross_occurrence_topn(primary, secondary, top_n=2)
        assert scores.shape == (2, 2) and idx.shape == (2, 2)
        # item 0's top correlator is thing 1; item 1's is thing 0
        assert idx[0, 0] == 1
        assert idx[1, 0] == 0
        assert scores[0, 0] > 0

    def test_no_cooccurrence_no_correlator(self):
        primary = cco.edges_to_indicator(np.array([0]), np.array([0]), 4, 1)
        secondary = cco.edges_to_indicator(np.array([1]), np.array([0]), 4, 1)
        scores, idx = cco.cross_occurrence_topn(primary, secondary, top_n=1)
        assert idx[0, 0] == -1  # never co-occurred → not a correlator

    def test_self_indicator_excludes_diagonal(self):
        # users 0-1 buy items {0,1} together; users 2-3 buy item 2 only —
        # so 0↔1 co-occurrence is informative (not universal)
        rows = np.array([0, 0, 1, 1, 2, 3])
        cols = np.array([0, 1, 0, 1, 2, 2])
        p = cco.edges_to_indicator(rows, cols, 4, 3)
        scores, idx = cco.cross_occurrence_topn(
            p, p, top_n=2, self_indicator=True
        )
        assert idx[0, 0] == 1  # item 0's correlator is item 1, not itself
        assert idx[1, 0] == 0
        assert 0 not in idx[0][idx[0] >= 0] or idx[0, 0] != 0  # no diagonal

    def test_uninformative_cooccurrence_scores_zero(self):
        """Everyone does everything → LLR = 0 → no correlators."""
        u = np.ones((8, 2), dtype=np.float32)
        scores, idx = cco.cross_occurrence_topn(u, u, top_n=2)
        assert (idx == -1).all()

    def test_score_history(self):
        idx = np.array([[1, 3, -1], [2, -1, -1]])
        vals = np.array([[2.0, 1.0, 9.9], [5.0, 9.9, 9.9]], dtype=np.float32)
        s = cco.score_history(idx, vals, np.array([3, 2]))
        assert s[0] == pytest.approx(1.0)  # hit on correlator 3 only
        assert s[1] == pytest.approx(5.0)  # hit on correlator 2
        assert cco.score_history(idx, vals, np.empty(0, int)).sum() == 0

    def test_mesh_sharded_matches_single(self, mesh8):
        # 17 users: deliberately NOT divisible by 8 — exercises padding
        rng = np.random.RandomState(0)
        p = (rng.rand(17, 6) > 0.5).astype(np.float32)
        s = (rng.rand(17, 5) > 0.5).astype(np.float32)
        v0, i0 = cco.cross_occurrence_topn(p, s, top_n=3)
        v1, i1 = cco.cross_occurrence_topn(p, s, top_n=3, mesh=mesh8)
        np.testing.assert_allclose(v0, v1, atol=1e-5)
        np.testing.assert_array_equal(i0, i1)


# ---------------------------------------------------------------------------
# engine end-to-end
# ---------------------------------------------------------------------------


UR_VARIANT = {
    "id": "ur",
    "engineFactory": "predictionio_tpu.engines.universal.UniversalRecommenderEngine",
    "datasource": {
        "params": {"app_name": "urapp", "indicators": ["buy", "view"]}
    },
    "algorithms": [
        {
            "name": "ur",
            "params": {"app_name": "urapp", "max_correlators_per_item": 10},
        }
    ],
}


@pytest.fixture()
def ur_storage(fresh_storage):
    """Cohort structure across two indicator types: even users buy items
    0-3 and view accessories a0-a1; odd users buy 4-7 and view a2-a3."""
    app_id = fresh_storage.get_meta_data_apps().insert(App(id=0, name="urapp"))
    fresh_storage.get_events().init_app(app_id)
    rng = np.random.RandomState(17)
    events = []
    for u in range(20):
        g = u % 2
        for _ in range(6):
            events.append(
                Event(event="buy", entity_type="user", entity_id=f"u{u}",
                      target_entity_type="item",
                      target_entity_id=f"i{rng.randint(0, 4) + g * 4}")
            )
        for _ in range(4):
            events.append(
                Event(event="view", entity_type="user", entity_id=f"u{u}",
                      target_entity_type="item",
                      target_entity_id=f"a{rng.randint(0, 2) + g * 2}")
            )
    fresh_storage.get_events().insert_batch(events, app_id)
    return fresh_storage, app_id


def deploy_ur(storage):
    inst = run_train(storage, UR_VARIANT)
    assert inst.status == "COMPLETED"
    engine, ep, models = prepare_deploy_models(storage, inst)
    algo = engine.make_algorithms(ep)[0]
    algo.set_serving_context(RuntimeContext(storage=storage, mode="serve"))
    return algo, models[0]


class TestUniversalRecommender:
    def test_recommends_cohort_items(self, ur_storage):
        storage, _ = ur_storage
        algo, model = deploy_ur(storage)
        from predictionio_tpu.engines.universal import Query

        pred = algo.predict(model, Query(user="u0", num=4, exclude_seen=False))
        assert pred.item_scores
        items = {s.item for s in pred.item_scores}
        assert items <= {"i0", "i1", "i2", "i3"}, items

    def test_exclude_seen_primary(self, ur_storage):
        storage, app_id = ur_storage
        algo, model = deploy_ur(storage)
        from predictionio_tpu.engines.universal import Query
        from predictionio_tpu.data.store.event_store import EventStoreFacade

        seen = {
            e.target_entity_id
            for e in EventStoreFacade(storage).find_by_entity(
                app_name="urapp", entity_type="user", entity_id="u0",
                event_names=["buy"],
            )
        }
        pred = algo.predict(model, Query(user="u0", num=8, exclude_seen=True))
        assert not ({s.item for s in pred.item_scores} & seen)

    def test_secondary_indicator_contributes(self, ur_storage):
        """A user with ONLY view history (no buys) still gets cohort
        recommendations via the view indicator — the point of multi-modal
        CCO."""
        storage, app_id = ur_storage
        algo, model = deploy_ur(storage)
        storage.get_events().insert_batch(
            [
                Event(event="view", entity_type="user", entity_id="lurker",
                      target_entity_type="item", target_entity_id="a0"),
                Event(event="view", entity_type="user", entity_id="lurker",
                      target_entity_type="item", target_entity_id="a1"),
            ],
            app_id,
        )
        from predictionio_tpu.engines.universal import Query

        pred = algo.predict(model, Query(user="lurker", num=4))
        assert pred.item_scores, "view-only user should get recommendations"
        items = {s.item for s in pred.item_scores}
        assert items <= {"i0", "i1", "i2", "i3"}, items

    def test_secondary_only_indicators_with_exclude_seen(self, ur_storage):
        """Keeping only the secondary indicator must still filter seen
        items in the PRIMARY item space (vocabulary mismatch regression)."""
        storage, _ = ur_storage
        variant = dict(UR_VARIANT)
        variant["algorithms"] = [
            {
                "name": "ur",
                "params": {
                    "app_name": "urapp",
                    "max_correlators_per_item": 10,
                    "indicators": ["view"],
                },
            }
        ]
        inst = run_train(storage, variant)
        engine, ep, models = prepare_deploy_models(storage, inst)
        algo = engine.make_algorithms(ep)[0]
        algo.set_serving_context(RuntimeContext(storage=storage, mode="serve"))
        from predictionio_tpu.engines.universal import Query
        from predictionio_tpu.data.store.event_store import EventStoreFacade

        seen = {
            e.target_entity_id
            for e in EventStoreFacade(storage).find_by_entity(
                app_name="urapp", entity_type="user", entity_id="u0",
                event_names=["buy"],
            )
        }
        pred = algo.predict(model=models[0], query=Query(user="u0", num=8))
        items = {s.item for s in pred.item_scores}
        assert not (items & seen)
        # recommendations still flow from the view indicator
        pred2 = algo.predict(models[0], Query(user="u0", num=8, exclude_seen=False))
        assert pred2.item_scores

    def test_unknown_user_empty(self, ur_storage):
        storage, _ = ur_storage
        algo, model = deploy_ur(storage)
        from predictionio_tpu.engines.universal import Query

        assert algo.predict(model, Query(user="ghost")).item_scores == []

    def test_self_cleaning_window_wired(self, ur_storage):
        storage, app_id = ur_storage
        # duplicate events + old events to clean
        import datetime as dt

        old = dt.datetime.now(dt.timezone.utc) - dt.timedelta(days=90)
        storage.get_events().insert(
            Event(event="buy", entity_type="user", entity_id="u0",
                  target_entity_type="item", target_entity_id="i0",
                  event_time=old),
            app_id,
        )
        variant = dict(UR_VARIANT)
        variant["datasource"] = {
            "params": {
                "app_name": "urapp",
                "indicators": ["buy", "view"],
                "event_window": {
                    "duration": "30 days",
                    "remove_duplicates": True,
                },
            }
        }
        inst = run_train(storage, variant)
        assert inst.status == "COMPLETED"
        # the 90-day-old event was aged out of the store
        from predictionio_tpu.data.storage.base import EventQuery

        remaining = [
            e for e in storage.get_events().find(EventQuery(app_id=app_id))
            if e.event_time <= old
        ]
        assert remaining == []


class TestDeviceBatchServing:
    """VERDICT r2 #5: the UR serving hot path is one device dispatch."""

    def _tables(self, rng, n_items, n_things, top_n):
        idx = rng.randint(0, n_things, (n_items, top_n)).astype(np.int32)
        # -1-pad a ragged tail like real correlator tables
        for i in range(0, n_items, 3):
            idx[i, top_n // 2:] = -1
        scores = rng.rand(n_items, top_n).astype(np.float32) + 0.1
        scores[idx < 0] = 0.0
        return idx, scores

    def test_batch_matches_score_history_reference(self):
        from predictionio_tpu.models import cco

        rng = np.random.RandomState(5)
        n_items = 500
        tables = [
            self._tables(rng, n_items, n_things, 16) + (n_things,)
            for n_things in (300, 120)
        ]
        B, H = 6, 20
        hists = []
        for _, _, j in tables:
            h = np.full((B, H), -1, np.int32)
            for b in range(B):
                n = rng.randint(0, H)
                h[b, :n] = rng.randint(0, j, n)
            hists.append(h)
        exclude = np.full((B, 8), -1, np.int32)
        exclude[0, :3] = [1, 2, 3]
        staged = cco.stage_correlators(tables)
        vals, idx = cco.batch_score_topk(
            staged, cco.plan_windows(staged, hists),
            cco.exclusion_of([[1, 2, 3]], B, staged.rows_padded), B,
            k=n_items,
        )

        for b in range(B):
            expect = np.zeros(n_items, np.float32)
            for (cidx, csc, _j), h in zip(tables, hists):
                hh = h[b][h[b] >= 0]
                expect += cco.score_history(cidx, csc, hh)
            got = np.zeros(n_items, np.float32)
            got[idx[b]] = np.maximum(vals[b], 0.0)
            expect[exclude[b][exclude[b] >= 0]] = 0.0  # device masks these
            np.testing.assert_allclose(got, expect, rtol=1e-5, atol=1e-5)

    def test_catalog_scale_qps(self):
        """10^5-item catalog: the batched program must sustain real
        throughput (measured on the CPU test backend; on the chip: not
        measured)."""
        import time

        from predictionio_tpu.models import cco

        rng = np.random.RandomState(9)
        n_items = 100_000
        cidx, csc = self._tables(rng, n_items, 80_000, 50)
        # device-resident tables, as URModel.resident stages them —
        # re-uploading 20 MB of correlators per batch is NOT the product
        # configuration
        tables = cco.stage_correlators([(cidx, csc, 80_000)])
        B, H = 64, 100
        hist = np.full((B, H), -1, np.int32)
        for b in range(B):
            hist[b] = rng.randint(0, 80_000, H)
        exclude = cco.Exclusion("none", None)
        plan = cco.plan_windows(tables, [hist])
        vals, idx = cco.batch_score_topk(tables, plan, exclude, B, k=64)  # warm
        t0 = time.perf_counter()
        n_reps = 3
        for _ in range(n_reps):
            vals, idx = cco.batch_score_topk(tables, plan, exclude, B, k=64)
        dt = (time.perf_counter() - t0) / n_reps
        qps = B / dt
        assert vals.shape == (B, 64)
        # CPU-backend floor; the device path exists precisely so this does
        # not degrade to per-(query x indicator) numpy loops
        assert qps > 40, f"batched UR qps {qps:.0f}"


def test_blocked_cco_matches_unblocked():
    """Item-blocked CCO (the 1e5-catalog HBM fix) is exact vs single-shot."""
    import numpy as np

    from predictionio_tpu.models import cco

    rng = np.random.RandomState(4)
    P = (rng.rand(60, 300) < 0.1).astype(np.float32)
    S = (rng.rand(60, 150) < 0.15).astype(np.float32)
    for self_ind, sec in ((True, P), (False, S)):
        v1, i1 = cco.cross_occurrence_topn(P, sec, 8, self_indicator=self_ind)
        v2, i2 = cco.cross_occurrence_topn(
            P, sec, 8, self_indicator=self_ind, block_items=64
        )
        np.testing.assert_allclose(v1, v2, rtol=1e-6)
        assert (i1 == i2).all()
