"""Continuous batching, tenant-aware drain, and the serve-dtype /
sharded-similar engine wiring (ISSUE 11).

The dispatcher tests drive `_BatchDispatcher` directly with a fake
runtime whose batch_predict sleeps — the same harness shape
test_query_server uses for its drain tests — so batching decisions are
observable as recorded batch sizes rather than wall-clock flakiness
wherever possible. The close rule's tests (ISSUE 38) go further: their
batch_predict blocks on an event the test sets, so they hold whatever
the clock does."""

import sys
import threading
import time

import numpy as np
import pytest

from predictionio_tpu.workflow import server as S


class _Owner:
    metrics = None
    tenant_weight = None

    def bookkeep_predict(self, s, n):
        pass

    def count_shed(self, r):
        pass


class _Serving:
    def serve(self, q, preds):
        return preds[0]


def _runtime(device_s=0.0):
    class _Algo:
        serving_context = None

        def batch_predict(self, ctx, model, queries):
            if device_s:
                time.sleep(device_s)
            return [(i, i) for i, _ in queries]

        def predict(self, model, query):
            return 0

    class _RT:
        algorithms = [_Algo()]
        models = [None]
        serving = _Serving()

    return _RT()


def _record_batches(d):
    sizes = []
    orig = d._run_group

    def wrap(rt, group):
        sizes.append(len(group))
        return orig(rt, group)

    d._run_group = wrap
    return sizes


def test_batching_mode_validated():
    with pytest.raises(ValueError):
        S._BatchDispatcher(_Owner(), 2.0, 64, 60.0, 1, batching="bogus")


def test_continuous_coalesces_arrivals_into_inflight_bucket():
    """With one slow bucket in flight, arrivals trickling in must join
    ONE assembling bucket that dispatches when the bucket ahead has its
    answers — the windowed drain at a short max_window splits the same
    stream into fragments."""

    def run(mode, max_window_ms):
        d = S._BatchDispatcher(
            _Owner(), 1.0, 64, max_window_ms, 1, batching=mode
        )
        sizes = _record_batches(d)
        rt = _runtime(device_s=0.25)
        threads = [
            threading.Thread(
                target=lambda: d.submit(object(), rt, timeout=10)
            )
        ]
        threads[0].start()
        time.sleep(0.05)  # bucket A is now in flight (sleeping)
        for _ in range(10):
            t = threading.Thread(
                target=lambda: d.submit(object(), rt, timeout=10)
            )
            t.start()
            threads.append(t)
            time.sleep(0.015)  # trickle while A flies
        for t in threads:
            t.join()
        d.stop()
        return sizes

    cont = run("continuous", 30.0)
    # bucket A (1 query) + ONE coalesced bucket for the trickle (a
    # straggler bucket can appear if the last arrival lands after A's
    # answers closed it)
    assert cont[0] == 1
    assert len(cont) <= 3, cont
    assert max(cont[1:]) >= 8, cont
    windowed = run("windowed", 30.0)
    # the 30 ms window fragments the 150 ms trickle into several buckets
    assert len(windowed) >= len(cont), (windowed, cont)


def test_continuous_answers_signal_counts():
    """Every batch handed over is counted as awaiting its answers and
    let go of once: three lone queries are three `idle_pipeline` batches
    and leave nothing awaited."""
    owner = _CountingOwner()
    d = S._BatchDispatcher(owner, 1.0, 8, 30.0, 2, batching="continuous")
    rt = _runtime()
    for _ in range(3):
        assert d.submit(object(), rt, timeout=5) == 0
    assert _closed(owner) == {"idle_pipeline": 3}
    _settle(d)
    assert not d._awaiting and d._active == 0
    d.stop()


# ---------------------------------------------------------------------------
# the close rule (ISSUE 38), on events the test sets
# ---------------------------------------------------------------------------

CLOSED_BY = {"full", "idle_pipeline", "answers_ready", "window", "wedge"}


class _CountingOwner(_Owner):
    """An owner with a registry: `dispatch_batches_closed_total` and the
    `batch_size` histogram land on it."""

    def __init__(self):
        from predictionio_tpu.obs.registry import MetricsRegistry

        self.metrics = MetricsRegistry()


def _closed(owner) -> dict:
    """{closed_by: batches} off the owner's registry."""
    fam = owner.metrics.counter(
        "dispatch_batches_closed_total", labelnames=("closed_by",))
    out = {c: int(fam.value(closed_by=c)) for c in CLOSED_BY}
    assert sum(out.values()) == fam.total  # no sixth word
    return {c: n for c, n in out.items() if n}


def _batches_run(owner) -> int:
    for fam in owner.metrics.families():
        if fam.name == "batch_size":
            return int(fam.count)
    return 0


def _wait_for(cond, what, timeout=10.0):
    deadline = time.monotonic() + timeout
    while not cond():
        assert time.monotonic() < deadline, f"timed out waiting for {what}"
        time.sleep(0.001)


def _settle(d):
    """Until every worker has let go of its slot."""
    _wait_for(lambda: d._active == 0, "the pool to drain")


class _Gated:
    """A runtime whose batch_predict blocks until the test opens that
    call's gate, and whose serve blocks while `serve_gate` is closed.
    It counts the calls inside batch_predict at once."""

    def __init__(self, calls=16):
        self.calls: list[list] = []       # the queries of each call
        self.entered = [threading.Event() for _ in range(calls)]
        self.gates = [threading.Event() for _ in range(calls)]
        self.serve_gate = threading.Event()
        self.serve_gate.set()
        self.lock = threading.Lock()
        self.inside = self.max_inside = 0
        gated = self

        class _Algo:
            serving_context = None

            def batch_predict(self, ctx, model, queries):
                with gated.lock:
                    n = len(gated.calls)
                    gated.calls.append([q for _, q in queries])
                    gated.inside += 1
                    gated.max_inside = max(gated.max_inside, gated.inside)
                gated.entered[n].set()
                try:
                    assert gated.gates[n].wait(20), "gate never opened"
                finally:
                    with gated.lock:
                        gated.inside -= 1
                return [(i, q) for i, q in queries]

            def predict(self, model, query):
                return query

        class _GatedServing:
            def serve(self, q, preds):
                assert gated.serve_gate.wait(20), "serve gate never opened"
                return preds[0]

        self.algorithms = [_Algo()]
        self.models = [None]
        self.serving = _GatedServing()

    def open_all(self):
        self.serve_gate.set()
        for g in self.gates:
            g.set()


def _submit_all(d, rt, queries, **kw):
    results = {}

    def one(q):
        try:
            results[q] = d.submit(q, rt, timeout=20, **kw)
        except Exception as e:  # the test reads it
            results[q] = e

    threads = [threading.Thread(target=one, args=(q,)) for q in queries]
    for t in threads:
        t.start()
    return threads, results


def _join(threads):
    for t in threads:
        t.join(20)
        assert not t.is_alive()


def _recorded_waits(d):
    """Every blocking `get` on the dispatcher's queue from here on, as
    (timeout, unless): the loop thread's only way to sleep."""
    waits = []
    real = d._queue.get

    def get(timeout=None, skip=None, unless=None):
        waits.append((timeout, unless))
        return real(timeout=timeout, skip=skip, unless=unless)

    d._queue.get = get
    return waits


def test_lone_query_on_idle_pipeline_is_handed_over_without_a_timed_wait():
    """The queue is dry and no batch awaits its answers: the batch
    closes NOW. The loop thread's only sleeps are its idle wait for a
    first query; `_collect` asks the queue for nothing it would have to
    wait for."""
    owner = _CountingOwner()
    d = S._BatchDispatcher(owner, 50.0, 8, 5000.0, 4)
    waits = _recorded_waits(d)
    rt = _Gated()
    rt.open_all()
    for q in ("a", "b", "c"):
        assert d.submit(q, rt, timeout=20) == q
        _settle(d)
    d.stop()
    assert rt.calls == [["a"], ["b"], ["c"]]
    assert _closed(owner) == {"idle_pipeline": 3}
    # the idle loop's wait for a first entry ends on stop(), nothing else
    assert waits and all(u == d._stop.is_set for _, u in waits), waits


def test_arrivals_join_one_batch_handed_over_when_the_answers_return():
    """While batch A's batch_predict is blocked, five arrivals join ONE
    assembling batch; it is handed over the moment A's batch_predict
    returns, although A's serve is still blocked (A is finishing on its
    own slot); and its own wait is the one sleep of the rule: timeout =
    the wedge deadline, woken by `_none_awaited`."""
    owner = _CountingOwner()
    d = S._BatchDispatcher(owner, 50.0, 64, 5000.0, 4)
    rt = _Gated()
    rt.serve_gate.clear()
    first, got_first = _submit_all(d, rt, ["A"])
    assert rt.entered[0].wait(10)
    waits = _recorded_waits(d)
    rest, got_rest = _submit_all(d, rt, [f"b{i}" for i in range(5)])
    _wait_for(lambda: d._queue.qsize() == 0 and d._held == 6,
              "the five to be taken")
    time.sleep(0.05)
    assert len(rt.calls) == 1  # held: the device is A's
    rt.gates[0].set()  # A's answers return; its serve stays blocked
    assert rt.entered[1].wait(10), "B was not handed over at A's answers"
    assert sorted(rt.calls[1]) == [f"b{i}" for i in range(5)]
    assert got_first == {} and first[0].is_alive()  # A is still finishing
    assert d._active == 2
    rt.open_all()
    _join(first + rest)
    _settle(d)
    d.stop()
    assert got_first == {"A": "A"}
    assert got_rest == {f"b{i}": f"b{i}" for i in range(5)}
    assert [len(c) for c in rt.calls] == [1, 5]
    assert rt.max_inside == 1
    assert _closed(owner) == {"idle_pipeline": 1, "answers_ready": 1}
    in_collect = [(t, u) for t, u in waits if u != d._stop.is_set]
    assert in_collect and all(
        u == d._none_awaited and 0.0 < t <= 10 * 5.0 for t, u in in_collect
    ), waits


def test_never_two_batches_between_hand_over_and_answers():
    """Forty threads, 400 queries, a thread switch every 10 µs, and no
    backlog that could fill a batch (`full` hands over whatever is
    awaited: the saturated regime keeps the pool's slots on the device
    stream): at no instant are two calls inside batch_predict, every
    query is answered with its own answer, nothing stays awaited or
    held, and the closed batches are the batches run."""
    owner = _CountingOwner()
    d = S._BatchDispatcher(owner, 1.0, 64, 30.0, 4)
    rt = _Gated(calls=400)
    rt.open_all()
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        wrong = []

        def client(k):
            for i in range(10):
                q = f"{k}-{i}"
                if d.submit(q, rt, timeout=20) != q:
                    wrong.append(q)

        threads = [threading.Thread(target=client, args=(k,))
                   for k in range(40)]
        for t in threads:
            t.start()
        _join(threads)
    finally:
        sys.setswitchinterval(old)
    _settle(d)
    d.stop()
    assert not wrong
    assert rt.max_inside == 1
    assert sum(len(c) for c in rt.calls) == 400
    assert not d._awaiting and d._held == 0
    closed = _closed(owner)
    assert set(closed) <= {"idle_pipeline", "answers_ready"}
    assert sum(closed.values()) == len(rt.calls) == _batches_run(owner)
    assert closed.get("answers_ready", 0) >= 1


@pytest.mark.parametrize("where", ["idle", "assembling"])
def test_stop_wakes_a_sleeping_loop(where):
    """stop() ends the loop thread where it sleeps — on the idle wait
    for a first query, or inside `_collect` behind a batch whose answers
    never come (its wedge deadline is 100 s away) — and fails the
    assembling query instead of holding it."""
    d = S._BatchDispatcher(_Owner(), 50.0, 8, 10_000.0, 2)
    rt = _Gated()
    threads, got = [], {}
    if where == "assembling":
        threads, got = _submit_all(d, rt, ["A"])
        assert rt.entered[0].wait(10)
        more, got_more = _submit_all(d, rt, ["B"])
        threads += more
        _wait_for(lambda: d._queue.qsize() == 0 and d._held == 2,
                  "B to be taken")
    else:
        time.sleep(0.05)  # the loop is in its idle wait
    d.stop()
    assert not d._thread.is_alive()
    if where == "assembling":
        _wait_for(lambda: "B" in got_more, "B to be failed")
        assert isinstance(got_more["B"], RuntimeError)
        rt.open_all()
        _join(threads)
        assert got == {"A": "A"}


def _close_by(branch, owner):
    """Drive one dispatcher so that a batch closes by `branch`."""
    rt = _Gated()
    if branch == "window":  # lingers 200 ms
        d = S._BatchDispatcher(owner, 1.0, 8, 200.0, 2, batching="windowed")
    elif branch == "wedge":  # wedged after 200 ms
        d = S._BatchDispatcher(owner, 1.0, 8, 20.0, 2)
    else:
        d = S._BatchDispatcher(owner, 50.0, 4, 5000.0, 2)
    threads, _ = _submit_all(d, rt, ["A"])
    assert rt.entered[0].wait(10)
    if branch != "idle_pipeline":
        more, _ = _submit_all(
            d, rt, [f"b{i}" for i in range(4 if branch == "full" else 2)])
        threads += more
        if branch == "answers_ready":
            _wait_for(lambda: d._queue.qsize() == 0 and d._held == 3,
                      "the two to be taken")
            rt.gates[0].set()
        # full, wedge and window close while A's batch_predict is blocked
        assert rt.entered[1].wait(10)
    rt.open_all()
    _join(threads)
    _settle(d)
    d.stop()
    return rt


@pytest.mark.parametrize(
    "branch", ["idle_pipeline", "answers_ready", "full", "wedge", "window"])
def test_every_branch_of_the_close_rule_is_counted(branch):
    """`dispatch_batches_closed_total{closed_by}`: each branch closes a
    batch under its own word, no batch under a sixth, and the counter's
    sum is the number of batches."""
    owner = _CountingOwner()
    rt = _close_by(branch, owner)
    closed = _closed(owner)
    assert set(closed) <= CLOSED_BY
    assert sum(closed.values()) == len(rt.calls) == _batches_run(owner)
    assert closed.pop("idle_pipeline") == 1  # the lone A
    if branch in ("wedge", "window"):
        # a timer's branch: on a slow hour the two arrivals may straddle
        # one deadline and close two batches under the same word
        assert set(closed) == {branch} and closed[branch] in (1, 2)
    else:
        assert closed == ({} if branch == "idle_pipeline" else {branch: 1})


def test_tenant_drain_closes_round_once_all_tenants_represented():
    """Windowed mode + tenants + a busy device: tenant_drain=True
    closes the assembling round the moment every backlogged tenant is
    represented — later arrivals form the NEXT round — while
    tenant_drain=False keeps lingering and absorbs them into one deep
    bucket. Asserted on batch composition, not wall-clock."""

    def run(tenant_drain):
        d = S._BatchDispatcher(
            _Owner(), 50.0, 64, 2000.0, 1, batching="windowed",
            tenant_drain=tenant_drain,
        )
        sizes = _record_batches(d)
        rt = _runtime(device_s=0.5)
        threads = [
            threading.Thread(
                target=lambda: d.submit(object(), rt, timeout=10)
            )
        ]
        threads[0].start()
        time.sleep(0.1)  # bucket A in flight for the next ~0.4 s
        for tid in ("t1", "t2"):
            t = threading.Thread(
                target=lambda tid=tid: d.submit(
                    object(), rt, timeout=10, tenant=tid
                )
            )
            t.start()
            threads.append(t)
        time.sleep(0.15)  # the tenant round assembles while A flies
        for _ in range(5):  # late arrivals, still before A retires
            t = threading.Thread(
                target=lambda: d.submit(object(), rt, timeout=10)
            )
            t.start()
            threads.append(t)
        for t in threads:
            t.join()
        d.stop()
        return sizes

    drained = run(True)
    # A=1, the closed tenant round of exactly 2, then the late 5
    assert drained[0] == 1
    assert 2 in drained, drained
    lingered = run(False)
    # the linger absorbed the late arrivals into one deep bucket
    assert max(lingered) >= 7, lingered


def test_fair_queue_backlogged():
    from predictionio_tpu.tenancy.fair import FairQueue

    class _Item:
        def __init__(self, tenant):
            self.tenant = tenant

    q = FairQueue()
    assert q.backlogged() == set()
    q.put(_Item(None))
    q.put(_Item("a"))
    assert q.backlogged() == {None, "a"}
    q.get_nowait()
    q.get_nowait()
    assert q.backlogged() == set()


# ---------------------------------------------------------------------------
# engine wiring: serve_dtype + sharded similar families
# ---------------------------------------------------------------------------


def _als_factors(rng, u=30, i=200, k=8):
    from predictionio_tpu.data.store.bimap import BiMap
    from predictionio_tpu.models import als

    return als.ALSFactors(
        user_factors=rng.standard_normal((u, k)).astype(np.float32),
        item_factors=rng.standard_normal((i, k)).astype(np.float32),
        user_vocab=BiMap({f"u{n}": n for n in range(u)}),
        item_vocab=BiMap({f"i{n}": n for n in range(i)}),
    )


def test_recommendation_serve_dtype_int8_end_to_end():
    from predictionio_tpu.engines.recommendation.engine import (
        ALSAlgorithm,
        ALSAlgorithmParams,
        ALSModel,
        Query,
    )

    rng = np.random.RandomState(0)
    f = _als_factors(rng)
    algo = ALSAlgorithm(ALSAlgorithmParams(serve_dtype="int8"))
    model = ALSModel(f, serve_dtype="int8")
    out = algo._predict_batch(
        model, [Query(user="u1", num=5), Query(user="u2", num=3,
                                               blacklist=["i0", "i1"])]
    )
    assert len(out[0].item_scores) == 5
    assert {s.item for s in out[1].item_scores}.isdisjoint({"i0", "i1"})
    # the staged state really is int8 and the cache charge halves
    sv = model.resident.get()
    assert sv.dtype == "int8" and str(sv.items.dtype) == "int8"
    f32_bytes = f.user_factors.nbytes + f.item_factors.nbytes
    assert model.resident_device_bytes() < f32_bytes


def _count_calls(monkeypatch, cls, name):
    calls = []
    real = getattr(cls, name)

    def counted(*a, **kw):
        calls.append(name)
        return real(*a, **kw)

    monkeypatch.setattr(cls, name, counted)
    return calls


def _big_vocab_case():
    """A 100,000-item model and queries with an unknown user and
    blacklists (known, last-row and unknown ids)."""
    from predictionio_tpu.engines.recommendation.engine import (
        ALSAlgorithm,
        ALSAlgorithmParams,
        ALSModel,
        Query,
    )

    f = _als_factors(np.random.RandomState(1), i=100_000)
    queries = [
        Query(user="u1", num=10),
        Query(user="nobody", num=10),
        Query(user="u2", num=7, blacklist=["i0", "i5", "i99999", "no-such"]),
        Query(user="u29", num=3),
        Query(user="u1", num=10, blacklist=["i3"]),
    ]
    return ALSAlgorithm(ALSAlgorithmParams()), ALSModel(f), f, queries


@pytest.mark.parametrize("call", ["predict_batch", "warmup"])
def test_serving_makes_no_copy_the_size_of_the_vocabulary(monkeypatch, call):
    """ISSUE 26: at 100,000 items neither a batch nor warm-up builds a
    BiMap through the copying constructor or lists one with to_dict()."""
    from predictionio_tpu.data.store.bimap import BiMap

    algo, model, _f, queries = _big_vocab_case()
    built = _count_calls(monkeypatch, BiMap, "__init__")
    listed = _count_calls(monkeypatch, BiMap, "to_dict")
    if call == "warmup":
        algo.warmup(model)
    else:
        out = algo._predict_batch(model, queries)
        assert [len(p.item_scores) for p in out] == [10, 0, 7, 3, 10]
    assert built == [] and listed == []


def test_replies_equal_the_copying_decodes(monkeypatch):
    """Same ids, same scores, same order as with the parent's inverse(),
    which built its result through the copying constructor."""
    from predictionio_tpu.data.store.bimap import BiMap

    algo, model, f, queries = _big_vocab_case()
    shared = algo._predict_batch(model, queries)
    monkeypatch.setattr(
        BiMap, "inverse",
        lambda self: BiMap({v: k for k, v in self.to_dict().items()}),
    )
    copied = algo._predict_batch(model, queries)

    def replies(preds):
        return [[(s.item, s.score) for s in p.item_scores] for p in preds]

    assert replies(shared) == replies(copied)
    # and they are the right replies: numpy's own ranking of the products
    scores = f.item_factors @ f.user_factors[2]
    scores[[0, 5, 99_999]] = -np.inf
    want = [f"i{ix}" for ix in np.argsort(-scores)[:7]]
    assert [s.item for s in shared[2].item_scores] == want
    assert shared[1].item_scores == []
    without_i3 = [s.item for s in shared[0].item_scores if s.item != "i3"]
    assert [s.item for s in shared[4].item_scores][:9] == without_i3[:9]


def test_similarproduct_sharded_matches_host_ranking():
    jax = pytest.importorskip("jax")
    if len(jax.devices()) < 2:
        pytest.skip("needs the forced multi-device CPU mesh")
    from predictionio_tpu.engines.similarproduct.engine import (
        ALSSimilarAlgorithm,
        ALSSimilarParams,
        Query,
        SimilarModel,
    )

    rng = np.random.RandomState(1)
    f = _als_factors(rng)
    q = Query(items=["i3", "i7"], num=6, blacklist=["i5"])
    host = ALSSimilarAlgorithm(ALSSimilarParams())
    host_model = SimilarModel(f)
    host_items = [
        s.item for s in host.predict(host_model, q).item_scores
    ]
    sharded = ALSSimilarAlgorithm(ALSSimilarParams(shard_serving=True))
    sh_model = SimilarModel(f)
    sh_out = sharded.predict(sh_model, q).item_scores
    sh_items = [s.item for s in sh_out]
    assert sh_model.sharded_info() is not None  # really went sharded
    assert sh_items == host_items
    assert "i5" not in sh_items and "i3" not in sh_items
    # SCORES must match too, not just the ranking — the same query
    # must not yield different values depending on device count
    host_scores = {
        s.item: s.score
        for s in host.predict(host_model, q).item_scores
    }
    for s in sh_out:
        assert abs(s.score - host_scores[s.item]) < 1e-4, (
            s.item, s.score, host_scores[s.item]
        )


def test_itemsim_sharded_on_the_fly_matches_precompute():
    jax = pytest.importorskip("jax")
    from predictionio_tpu.data.store.bimap import BiMap
    from predictionio_tpu.engines.itemsim.engine import (
        ItemSimAlgorithm,
        ItemSimAlgorithmParams,
        ItemSimModel,
        Query,
    )
    from predictionio_tpu.models import dimsum

    rng = np.random.RandomState(2)
    m = (rng.rand(40, 60) < 0.2).astype(np.float32)
    vocab = BiMap({f"i{n}": n for n in range(60)})
    scores, idx = dimsum.column_cosine_topn(m, top_n=60)
    pre = ItemSimModel(
        sim_scores=scores, sim_idx=idx, item_vocab=vocab, top_n=60
    )
    otf = ItemSimModel(
        sim_scores=np.zeros((0, 0), np.float32),
        sim_idx=np.zeros((0, 0), np.int64),
        item_vocab=vocab, top_n=60,
        item_vectors=np.ascontiguousarray(m.T),
    )
    algo = ItemSimAlgorithm(ItemSimAlgorithmParams(shard_serving=True))
    q = Query(items=["i3", "i9"], num=8)
    a = [s.item for s in algo.predict(pre, q).item_scores]
    b = [s.item for s in algo.predict(otf, q).item_scores]
    assert a == b
    if len(jax.devices()) >= 2:
        assert otf.sharded_info() is not None


def test_itemsim_model_unpickles_pre_issue11_state():
    """Models pickled before top_n/item_vectors existed must keep
    loading (the persisted-MODELDATA migration path) and serve via the
    precomputed-sim branch."""
    from predictionio_tpu.data.store.bimap import BiMap
    from predictionio_tpu.engines.itemsim.engine import (
        ItemSimAlgorithm,
        ItemSimAlgorithmParams,
        ItemSimModel,
        Query,
    )

    old_state = {
        "sim_scores": np.array([[0.9], [0.8]], np.float32),
        "sim_idx": np.array([[1], [0]], np.int64),
        "item_vocab": BiMap({"i0": 0, "i1": 1}),
    }
    model = ItemSimModel.__new__(ItemSimModel)
    model.__setstate__(old_state)
    assert model.top_n == 50 and model.item_vectors is None
    algo = ItemSimAlgorithm(ItemSimAlgorithmParams())
    out = algo.predict(model, Query(items=["i0"], num=1))
    assert [s.item for s in out.item_scores] == ["i1"]


def test_itemsim_sharded_model_pickles_without_runtime():
    import pickle

    from predictionio_tpu.data.store.bimap import BiMap
    from predictionio_tpu.engines.itemsim.engine import ItemSimModel

    m = np.eye(6, dtype=np.float32)
    model = ItemSimModel(
        sim_scores=np.zeros((0, 0), np.float32),
        sim_idx=np.zeros((0, 0), np.int64),
        item_vocab=BiMap({f"i{n}": n for n in range(6)}),
        top_n=3, item_vectors=m,
    )
    model.resident.get(shard=True)  # stages a tier (sharded on a mesh)
    assert model.resident.device_bytes() is not None
    clone = pickle.loads(pickle.dumps(model))
    assert clone.resident.device_bytes() is None  # nothing staged rides
    assert np.array_equal(clone.item_vectors, m)


def test_continuous_admission_caps_per_tenant():
    """ISSUE 14 satellite: while a bucket assembles in continuous mode
    with >1 tenant stream active, one tenant's backlog may claim at
    most max_batch // streams slots — the hog's overflow waits for the
    next bucket instead of filling this one ahead of other tenants."""
    d = S._BatchDispatcher(
        _Owner(), 1.0, 8, 30.0, 1, batching="continuous"
    )
    comps = []
    orig = d._run_group

    def wrap(rt, group):
        comps.append([p.tenant for p in group])
        return orig(rt, group)

    d._run_group = wrap
    rt = _runtime(device_s=0.3)
    threads = [
        threading.Thread(
            target=lambda: d.submit(object(), rt, timeout=10)
        )
    ]
    threads[0].start()
    time.sleep(0.05)  # bucket A in flight — the assembly window opens
    # both streams must be VISIBLE (queued) before the hog backlog can
    # fill the bucket, so the goods go first — the cap engages as soon
    # as more than one stream is active
    for tenant in ["good"] * 2 + ["hog"] * 10:
        t = threading.Thread(
            target=lambda tn=tenant: d.submit(
                object(), rt, timeout=10, tenant=tn
            )
        )
        t.start()
        threads.append(t)
        if tenant == "good":
            time.sleep(0.01)
    time.sleep(0.1)  # everything queued while A still flies
    for t in threads:
        t.join()
    d.stop()
    # bucket A is the solo blocker; the first capped bucket holds BOTH
    # good queries and at most 8 // 2 = 4 hog entries; hog overflow
    # lands in later buckets
    assert comps[0] == [None]
    first = comps[1]
    assert first.count("good") == 2, comps
    assert first.count("hog") <= 4, comps
    assert sum(c.count("hog") for c in comps) == 10


def test_admission_cap_noop_for_single_stream():
    """A solo tenant (or untenanted traffic) keeps the whole bucket —
    the cap only engages with competing streams."""
    d = S._BatchDispatcher(
        _Owner(), 1.0, 8, 30.0, 1, batching="continuous"
    )
    sizes = _record_batches(d)
    rt = _runtime(device_s=0.25)
    threads = [
        threading.Thread(
            target=lambda: d.submit(object(), rt, timeout=10, tenant="t")
        )
    ]
    threads[0].start()
    time.sleep(0.05)
    for _ in range(7):
        t = threading.Thread(
            target=lambda: d.submit(object(), rt, timeout=10, tenant="t")
        )
        t.start()
        threads.append(t)
    time.sleep(0.05)
    for t in threads:
        t.join()
    d.stop()
    assert sizes[0] == 1
    assert max(sizes[1:]) == 7, sizes  # uncapped single stream
