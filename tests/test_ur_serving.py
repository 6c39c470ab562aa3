"""The Universal Recommender's serving path against the benchmark's plain
reference (`benchmarks/reference/ur_scores.py`), small sizes, CPU: the
scoring program on both tails, the exclusion forms a batch's ids pick, the
engine through a `memory` event store, staging under the resident budget,
and the spans and counters the chip cell's per-layer metrics read."""

import datetime as dt
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.reference import ur_scores as ref  # noqa: E402
from predictionio_tpu.core.base import RuntimeContext  # noqa: E402
from predictionio_tpu.data.event import Event  # noqa: E402
from predictionio_tpu.data.storage.base import App  # noqa: E402
from predictionio_tpu.data.storage.registry import (  # noqa: E402
    SourceConfig,
    Storage,
    StorageConfig,
)
from predictionio_tpu.data.store.bimap import BiMap  # noqa: E402
from predictionio_tpu.engines.universal import engine as ur  # noqa: E402
from predictionio_tpu.fleet.runtime import OversizedModelError  # noqa: E402
from predictionio_tpu.models import cco  # noqa: E402
from predictionio_tpu.models.resident import ResidentCorrelators  # noqa: E402
from predictionio_tpu.obs import spans as _spans  # noqa: E402
from predictionio_tpu.ops import recommend_pallas as rp  # noqa: E402

#: no tile of the kernel divides it
N_ITEMS = 8192 + 777
ROWS = rp.pad_items(N_ITEMS)
TOP_N = 6
DEPTH = 100
INDICATORS = ("buy", "pv", "cart")


def make_tables(seed=3, n_items=N_ITEMS):
    rng = np.random.default_rng(seed)
    tables = []
    for _ in INDICATORS:
        idx = rng.integers(0, n_items, (n_items, TOP_N)).astype(np.int32)
        idx[rng.random((n_items, TOP_N)) < 0.15] = -1
        w = (0.25 + rng.standard_exponential((n_items, TOP_N))).astype(np.float32)
        w[idx < 0] = 0.0
        tables.append((idx, w))
    return tables


@pytest.fixture(scope="module")
def tables():
    return make_tables()


@pytest.fixture(scope="module")
def staged(tables):
    return cco.stage_correlators([(i, w, N_ITEMS) for i, w in tables])


def user_histories(rng, lengths):
    """One user's targets per indicator, oldest first, distinct."""
    return [rng.choice(N_ITEMS, n, replace=False) for n in lengths]


def as_batch(per_user, bsz):
    """Per indicator a (bsz, DEPTH) int32 array, -1 padded, newest first as
    the engine reads them."""
    out = []
    for m in range(len(INDICATORS)):
        h = np.full((bsz, DEPTH), -1, np.int32)
        for b, hists in enumerate(per_user):
            recent = ref.latest(hists[m], DEPTH)[::-1]
            h[b, : len(recent)] = recent
        out.append(h)
    return out


def score(staged, per_user, bsz, exclude, k, mode):
    """The scoring program on a batch of users' histories: the plan made
    as the engine makes it."""
    plan = cco.plan_windows(staged, as_batch(per_user, bsz))
    return cco.batch_score_topk(staged, plan, exclude, bsz, k, mode=mode)


def test_staging_inverts_each_table_once(tables, staged):
    """The postings of every indicator, one block of the two resident
    arrays each, sorted by thing: a thing's list is every (item, weight)
    that names it, the -1 slots are never in one, a window of padding
    closes the arrays, and the total the program scores into is padded so
    that a kernel tile divides it."""
    assert ROWS == 9088 and rp.pick_item_tile(ROWS) == 128  # the pad rule's
    assert staged.rows_padded == ROWS and staged.n_items == N_ITEMS
    assert int(staged.n_items_device) == N_ITEMS
    slots = 3 * N_ITEMS * TOP_N
    assert staged.items.shape == staged.weights.shape == (slots + cco._WINDOW,)
    items, weights = np.asarray(staged.items), np.asarray(staged.weights)
    for m, (offsets, (idx, w)) in enumerate(zip(staged.offsets, tables)):
        assert offsets.shape == (N_ITEMS + 1,) and offsets.dtype == np.int64
        base = m * N_ITEMS * TOP_N
        assert offsets[0] == base + (idx < 0).sum()
        assert offsets[-1] == base + idx.size
        for thing in (0, 17, N_ITEMS - 1, int(idx[5, 0])):
            lo, hi = offsets[thing], offsets[thing + 1]
            rows, cols = np.nonzero(idx == thing)
            assert hi - lo == len(rows)
            got = sorted(zip(items[lo:hi].tolist(), weights[lo:hi].tolist()))
            assert got == sorted(zip(rows.tolist(), w[rows, cols].tolist()))
    assert staged.nbytes == cco.table_set_bytes(
        [(i, w, N_ITEMS) for i, w in tables]) == (slots + cco._WINDOW) * 8


def test_the_plan_reads_each_distinct_thing_once_in_windows(staged):
    """`plan_windows`: a thing seen twice in a history is read once, a list
    longer than a window is cut, the last window's `valid` is the rest;
    `plan_calls` cuts the plan into calls of the bucket's size."""
    offsets = staged.offsets[1]
    lens = np.diff(offsets)
    long_thing = int(np.argmax(lens))
    hist = np.full((8, DEPTH), -1, np.int32)
    hist[0, :3] = [5, 5, long_thing]
    hist[2, 0] = 7
    empty = np.full((8, DEPTH), -1, np.int32)
    plan = cco.plan_windows(staged, [empty, hist, empty])
    want = []
    for row, thing in ((0, 5), (0, long_thing), (2, 7)):
        for off in range(0, int(lens[thing]), cco._WINDOW):
            want.append((row, offsets[thing] + off,
                         min(cco._WINDOW, lens[thing] - off)))
    assert sorted(map(tuple, plan.tolist())) == sorted(want)
    assert cco.plan_windows(staged, [empty] * 3).shape == (0, 3)
    assert [cco.call_windows(b) for b in (1, 8, 64)] == [256, 1024, 4096]
    assert [c.shape for c in cco.plan_calls(plan, 1)] == [(256, 3)]
    assert [c.shape for c in cco.plan_calls(np.ones((0, 3), np.int64), 8)] == [
        (1024, 3)]
    calls = cco.plan_calls(np.ones((256 + 3, 3), np.int64), 1)
    assert [c.shape[0] for c in calls] == [256, 256]
    assert calls[0].dtype == np.int32 and calls[1][:3].all()
    assert not calls[1][3:].any()  # dead windows: valid 0


@pytest.mark.parametrize("mode", ["off", "interpret"])
@pytest.mark.parametrize("n_excluded,form", [(0, "none"), (5, "rows"),
                                             (40, "rows"), (70, "mask")])
def test_scoring_program_matches_the_reference(tables, staged, mode,
                                               n_excluded, form):
    """Scores and order of the program's top-k against the reference, one
    query at a time: an empty history, a history of exactly DEPTH, one
    longer than DEPTH (the latest DEPTH count), and a short one; the
    exclusion in the form the batch's ids call for."""
    rng = np.random.default_rng(11 + n_excluded)
    per_user = [
        user_histories(rng, (0, 0, 0)),
        user_histories(rng, (DEPTH, DEPTH, 3)),
        user_histories(rng, (2, DEPTH + 37, 0)),
        user_histories(rng, (1, 9, 5)),
    ]
    bsz, k = 8, 64
    lists = [sorted(rng.choice(N_ITEMS, n_excluded, replace=False).tolist())
             for _ in per_user]
    lists[3] = lists[3][: n_excluded // 2]  # the widest list picks the form
    exclude = cco.exclusion_of(lists, bsz, staged.rows_padded)
    assert exclude.form == form
    if form == "rows":
        assert exclude.array.shape == (bsz, 8 if n_excluded <= 8 else 64)
    if form == "mask":
        assert exclude.array.shape == (bsz, staged.rows_padded // 32)
    vals, idx = score(staged, per_user, bsz, exclude, k, mode)
    assert vals.shape == idx.shape == (bsz, k)
    for b, hists in enumerate(per_user):
        total = ref.scores(tables, hists, DEPTH)
        want_rows, want = ref.top(total, lists[b], k)
        n = len(want_rows)
        got_rows, got = idx[b][vals[b] > 0], vals[b][vals[b] > 0]
        assert len(got) == n and (n > 0) == (b > 0)
        np.testing.assert_allclose(got, want, rtol=2e-6)
        # same items; an order that differs does so only between scores
        # that agree to the rounding of a float32 sum
        assert set(got_rows.tolist()) == set(want_rows.tolist()) or (
            np.allclose(np.sort(total[got_rows]), np.sort(want), rtol=2e-6))
        assert not set(got_rows.tolist()) & set(lists[b])
        assert (got_rows < N_ITEMS).all()


def test_a_batch_with_more_windows_than_one_call_takes(tables, staged, monkeypatch):
    """Long histories over long lists make further accumulate calls into
    the same total: the answer is the reference's all the same."""
    monkeypatch.setattr(cco, "_CALL_WINDOWS", (8, 32))
    monkeypatch.setattr(cco, "_WINDOWS_PER_ROW", 1)
    rng = np.random.default_rng(31)
    per_user = [user_histories(rng, (DEPTH, DEPTH, DEPTH)) for _ in range(3)]
    plan = cco.plan_windows(staged, as_batch(per_user, 8))
    assert len(cco.plan_calls(plan, 8)) > 5
    vals, idx = score(staged, per_user, 8, cco.Exclusion("none", None), 32,
                      "off")
    for b, hists in enumerate(per_user):
        want_rows, want = ref.top(ref.scores(tables, hists, DEPTH), [], 32)
        np.testing.assert_allclose(vals[b], want, rtol=2e-6)
        assert np.allclose(ref.scores(tables, hists, DEPTH)[idx[b]], want,
                           rtol=2e-6)


def test_packed_words_and_row_list_agree(staged):
    """The same ids in either wire form give the same answer."""
    rng = np.random.default_rng(5)
    per_user = [user_histories(rng, (4, 60, 7)) for _ in range(2)]
    lists = [rng.choice(N_ITEMS, 30, replace=False).tolist() for _ in per_user]
    rows = cco.exclusion_of(lists, 8, staged.rows_padded)
    words = cco.Exclusion("mask", rp.pack_mask_np(
        _mask_of(lists, 8, staged.rows_padded), staged.rows_padded))
    # the words `exclusion_of` builds straight from ids (here the same ids,
    # one repeated until the list outgrows a row list) are the packed mask
    repeated = cco.exclusion_of(
        [ids + ids[:1] * 40 for ids in lists], 8, staged.rows_padded)
    assert repeated.form == "mask"
    assert np.array_equal(words.array, repeated.array)
    a = score(staged, per_user, 8, rows, 16, "off")
    b = score(staged, per_user, 8, words, 16, "off")
    assert np.array_equal(a[1], b[1]) and np.array_equal(a[0], b[0])


def _mask_of(lists, bsz, rows):
    mask = np.zeros((bsz, rows), bool)
    for b, ids in enumerate(lists):
        mask[b, ids] = True
    return mask


# -- the engine, through a memory event store -------------------------------


def memory_storage():
    storage = Storage(StorageConfig(
        sources={"MEM": SourceConfig("MEM", "memory", {})},
        repositories={"METADATA": "MEM", "EVENTDATA": "MEM",
                      "MODELDATA": "MEM"}))
    app_id = storage.get_meta_data_apps().insert(App(id=0, name="urtest"))
    storage.get_events().init_app(app_id)
    return storage, app_id


@pytest.fixture(scope="module")
def deployed(tables):
    storage, app_id = memory_storage()
    vocab = BiMap({f"i{i}": i for i in range(N_ITEMS)})
    model = ur.URModel(
        item_vocab=vocab,
        indicator_models=[
            ur.IndicatorModel(name=name, correlator_scores=w,
                              correlator_idx=idx, target_vocab=vocab)
            for name, (idx, w) in zip(INDICATORS, tables)],
        primary_indicator="buy")
    algo = ur.URAlgorithm(ur.URAlgorithmParams(
        app_name="urtest", max_correlators_per_item=TOP_N,
        max_query_events=DEPTH))
    algo.set_serving_context(RuntimeContext(storage=storage, mode="serve"))
    rng = np.random.default_rng(23)
    users = {"heavy": user_histories(rng, (3, DEPTH + 20, 8)),
             "light": user_histories(rng, (0, 12, 1)),
             "buyer": user_histories(rng, (70, 30, 0))}
    t0 = dt.datetime(2017, 11, 25, tzinfo=dt.timezone.utc)
    events = []
    for user, hists in users.items():
        for name, targets in zip(INDICATORS, hists):
            for j, item in enumerate(targets):
                events.append(Event(
                    event=name, entity_type="user", entity_id=user,
                    target_entity_type="item", target_entity_id=f"i{item}",
                    event_time=t0 + dt.timedelta(seconds=j)))
    storage.get_events().insert_batch(events, app_id)
    return algo, model, users


@pytest.mark.parametrize("user,blacklist", [
    ("heavy", 0), ("heavy", 6), ("light", 0), ("buyer", 3), ("nobody", 0)])
def test_engine_predict_matches_the_reference(deployed, tables, user, blacklist):
    """`_predict_batch` end to end: histories read from the memory store
    (the latest DEPTH of a type), seen buys and the blacklist removed."""
    algo, model, users = deployed
    hists = users.get(user, user_histories(np.random.default_rng(0), (0, 0, 0)))
    total = ref.scores(tables, hists, DEPTH)
    black = [int(i) for i in np.argsort(-total)[:blacklist]]
    query = ur.Query(user=user, num=20, blacklist=[f"i{i}" for i in black])
    # in a batch with others, so the bucket's pad rows are exercised too
    results = algo._predict_batch(
        algo.serving_context, model,
        [ur.Query(user="light", num=5), query, ur.Query(user="buyer", num=7)])
    assert [len(r.item_scores) for r in results][::2] == [5, 7]
    got = results[1].item_scores
    dead = set(black) | set(int(i) for i in ref.latest(hists[0], DEPTH))
    want_rows, want = ref.top(total, dead, 20)
    assert len(got) == len(want_rows) and (len(got) == 20) == (user != "nobody")
    np.testing.assert_allclose([s.score for s in got], want, rtol=2e-6)
    assert [s.item for s in got] == [f"i{i}" for i in want_rows]
    if user == "buyer":  # 70 seen buys + 3: beyond a row list, packed words
        assert len(dead) > rp.ROWLIST_MAX


def test_warmup_stages_once_and_covers_every_form(deployed, monkeypatch):
    """Warm-up compiles every (bucket, exclusion form) program, so that no
    batch of the forms traffic can pick compiles afterwards."""
    algo, model, _users = deployed
    seen = []
    real = cco.batch_score_topk

    rungs = []

    def spy(staged, plan, exclude, bsz, k, mode="auto"):
        seen.append((bsz, exclude.form,
                     None if exclude.array is None else exclude.array.shape[1]))
        rungs.append((bsz, k, len(cco.plan_calls(plan, bsz))))
        return real(staged, plan, exclude, bsz, k, mode)

    monkeypatch.setattr(cco, "batch_score_topk", spy)
    algo.warmup(model)
    words = ROWS // 32
    assert sorted(set(seen), key=str) == sorted(
        {(b, f, w) for b in (1, 8, 64)
         for f, w in (("none", None), ("rows", 8), ("rows", 64),
                      ("mask", words))}, key=str)
    # at the k live batches take; and each bucket's add-only program, by
    # a plan of two calls
    assert set(rungs) == {(b, 64, n) for b in (1, 8, 64) for n in (1, 2)}
    assert model.resident.get() is model.resident.get()


# -- staging under the resident budget ---------------------------------------


def test_staging_refuses_an_oversized_table_set_by_name(tables, monkeypatch):
    """The gate is the peak, not the resident size: a budget that holds
    the postings but not the staging sort beside them, or not the widest
    bucket's total, is refused before anything is put."""
    resident = ResidentCorrelators([(i, w, N_ITEMS) for i, w in tables])
    need = cco.table_set_bytes(resident.tables)
    terms = cco.device_peak_bytes(resident.tables)
    assert terms == (need, 4 * N_ITEMS * TOP_N * 4, cco.MAX_BATCH * ROWS * 4)
    peak = sum(terms)
    for budget in (need - 1, need + terms[1], peak - 1):
        monkeypatch.setenv("PIO_SERVE_HBM_BYTES", str(budget))
        with pytest.raises(OversizedModelError) as err:
            resident.get()
        said = str(err.value)
        assert "correlator tables" in said
        assert f"{len(INDICATORS)} indicators" in said
        for term in terms:  # the three terms, each by its size
            assert f"{term / 1e9:.2f}" in said
        assert resident.info() is None and resident.device_bytes() is None
    monkeypatch.setenv("PIO_SERVE_HBM_BYTES", str(peak))
    staged = resident.get()
    assert resident.get() is staged  # staged once
    assert resident.device_bytes() == need == staged.nbytes
    assert resident.info() == {
        "shards": 1, "n_items": N_ITEMS, "indicators": len(INDICATORS),
        "item_rows_padded": ROWS, "resident_bytes_total": need}
    resident.drop()
    assert resident.info() is None


def test_the_model_reports_its_resident_bytes(tables):
    vocab = BiMap({"i0": 0})
    model = ur.URModel(vocab, [ur.IndicatorModel(
        name="buy", correlator_scores=tables[0][1],
        correlator_idx=tables[0][0], target_vocab=vocab)], "buy")
    assert model.resident_device_bytes() == (
        tables[0][0].nbytes + tables[0][1].nbytes)
    model.resident.get()
    assert model.resident_device_bytes() == (
        N_ITEMS * TOP_N + cco._WINDOW) * 8
    import pickle

    again = pickle.loads(pickle.dumps(model))
    assert again.resident.info() is None  # staged state is never pickled


# -- spans and counters --------------------------------------------------------


def counter_values():
    from predictionio_tpu.obs.registry import get_default_registry

    fams = {f.name: f for f in get_default_registry().families()}
    return {
        "by_form": {form: fams["ur_batches_total"].value(form=form)
                    for form in ("none", "rows", "mask")},
        "bytes": fams["ur_exclusion_bytes_total"].total,
        "failures": fams["ur_history_read_failures_total"].total,
    }


def test_spans_and_counters_of_a_batch(deployed):
    algo, model, _users = deployed
    model.resident.drop()
    recorder = _spans.get_default_recorder()
    seen = []
    names = ("ur.stage", "ur.history_read", "ur.predict.prepare",
             "ur.predict.device", "ur.predict.decode")
    for name in names:
        recorder.bridge(name, seen.append)
    before = counter_values()
    try:
        algo._predict_batch(algo.serving_context, model, [
            ur.Query(user="heavy", num=20, exclude_seen=False)])
        algo._predict_batch(algo.serving_context, model, [
            ur.Query(user="heavy", num=20), ur.Query(user="light", num=20)])
        algo._predict_batch(algo.serving_context, model, [
            ur.Query(user="buyer", num=20)])
    finally:
        for name in names:
            recorder.unbridge(name)
    after = counter_values()
    by_name = {}
    for sp in seen:
        by_name.setdefault(sp.name, []).append(sp)
    assert [len(by_name[n]) for n in names] == [1, 3, 3, 3, 3]
    stage = by_name["ur.stage"][0]
    assert stage.attrs["bytes"] == (3 * N_ITEMS * TOP_N + cco._WINDOW) * 8
    assert stage.attrs["item_rows_padded"] == ROWS
    # heavy: 3 buys + the latest 100 of 120 views + 8 carts; light: 0 + 12 + 1
    assert [sp.attrs["events"] for sp in by_name["ur.history_read"]] == [
        111, 111 + 13, 70 + 30]
    assert [(sp.attrs["live"], sp.attrs["bucket"], sp.attrs["form"])
            for sp in by_name["ur.predict.prepare"]] == [
        (1, 1, "none"), (2, 8, "rows"), (1, 1, "mask")]
    # the windows a batch's histories name: the list of every distinct
    # thing of the user's latest 100, a type, in windows of `_WINDOW`
    heavy = _users["heavy"]
    named = sum(
        -(-int((idx == thing).sum()) // cco._WINDOW)
        for (idx, _w), h in zip(make_tables(), heavy)
        for thing in np.unique(ref.latest(h, DEPTH)))
    assert by_name["ur.predict.prepare"][0].attrs["windows"] == named > 0
    # the history read is a child of prepare: prepare covers it
    assert all(p.duration >= h.duration for p, h in zip(
        by_name["ur.predict.prepare"], by_name["ur.history_read"]))
    delta = {f: after["by_form"][f] - before["by_form"][f]
             for f in ("none", "rows", "mask")}
    assert delta == {"none": 1.0, "rows": 1.0, "mask": 1.0}
    assert after["bytes"] - before["bytes"] == 8 * 8 * 4 + 1 * (ROWS // 32) * 4
    assert after["failures"] == before["failures"]


def test_a_failed_history_read_is_counted_and_served_empty(deployed, monkeypatch):
    from predictionio_tpu.data.store import event_store

    algo, model, _users = deployed

    def broken(self, **kwargs):
        raise RuntimeError("store down")

    monkeypatch.setattr(event_store.EventStoreFacade, "find_by_entities", broken)
    before = counter_values()["failures"]
    result = algo._predict_batch(
        algo.serving_context, model, [ur.Query(user="heavy", num=20)])
    assert result[0].item_scores == []
    # one read an indicator failed; the seen list rides the primary's read
    assert counter_values()["failures"] - before == len(INDICATORS)
