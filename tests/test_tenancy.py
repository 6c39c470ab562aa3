"""Multi-tenant serving units (ISSUE 6): DRR fairness math, LRU cache
eviction rules (pinned / in-flight immunity), quota accounting, tenant
records, and the bounded tenant metric labels."""

import queue as stdlib_queue
import threading
import time

import pytest

from predictionio_tpu.obs.registry import MetricsRegistry
from predictionio_tpu.tenancy.cache import ModelCache, ModelLoadError
from predictionio_tpu.tenancy.fair import FairQueue
from predictionio_tpu.tenancy.quota import (
    QuotaEnforcer,
    QuotaExceeded,
    TokenBucket,
)
from predictionio_tpu.tenancy.tenants import Tenant, TenantStore


class _Item:
    def __init__(self, tenant, i):
        self.tenant = tenant
        self.i = i

    def __repr__(self):
        return f"{self.tenant}:{self.i}"


# ---------------------------------------------------------------------------
# deficit round robin
# ---------------------------------------------------------------------------


class TestFairQueue:
    def test_fifo_degenerate_single_stream(self):
        q = FairQueue()
        for i in range(10):
            q.put(_Item(None, i))
        assert [q.get_nowait().i for i in range(10)] == list(range(10))
        with pytest.raises(stdlib_queue.Empty):
            q.get_nowait()

    def test_hog_cannot_starve_light_tenants(self):
        """A 100-deep hog backlog vs two light tenants: the light
        tenants' items all drain within the first few rounds instead of
        waiting behind the whole hog queue (the FIFO failure mode)."""
        q = FairQueue()
        for i in range(100):
            q.put(_Item("hog", i))
        for i in range(5):
            q.put(_Item("a", i))
            q.put(_Item("b", i))
        drained = [q.get_nowait() for _ in range(110)]
        # equal weights: in the first 15 pops each tenant got ~5 slots,
        # so a and b are fully served almost immediately
        a_done = max(i for i, it in enumerate(drained) if it.tenant == "a")
        b_done = max(i for i, it in enumerate(drained) if it.tenant == "b")
        assert a_done < 16 and b_done < 16, (a_done, b_done)
        # and hog still got everything eventually, in its own order
        hog = [it.i for it in drained if it.tenant == "hog"]
        assert hog == list(range(100))

    def test_weights_scale_share(self):
        """weight=3 drains 3 slots per round against weight=1."""
        weights = {"heavy": 3.0, "light": 1.0}
        q = FairQueue(weight_of=lambda t: weights.get(t, 1.0))
        for i in range(30):
            q.put(_Item("heavy", i))
            q.put(_Item("light", i))
        first = [q.get_nowait() for _ in range(24)]
        heavy = sum(1 for it in first if it.tenant == "heavy")
        light = len(first) - heavy
        assert heavy == pytest.approx(18, abs=2), (heavy, light)

    def test_fractional_weights_make_progress(self):
        """Weights < 1 accumulate deficit over rotations instead of
        wedging the queue."""
        q = FairQueue(weight_of=lambda t: 0.3)
        for i in range(9):
            q.put(_Item("a", i))
            q.put(_Item("b", i))
        drained = [q.get_nowait() for _ in range(18)]
        assert len(drained) == 18
        assert q.qsize() == 0

    def test_blocking_get_timeout_and_wakeup(self):
        q = FairQueue()
        with pytest.raises(stdlib_queue.Empty):
            q.get(timeout=0.05)
        got = []

        def consumer():
            got.append(q.get(timeout=5.0))

        t = threading.Thread(target=consumer)
        t.start()
        time.sleep(0.05)
        q.put(_Item("x", 1))
        t.join(timeout=5.0)
        assert got and got[0].i == 1

    def test_idle_tenant_banks_no_priority(self):
        """A tenant whose queue drained and re-fills later competes
        fresh — it does not accumulate deficit while idle."""
        q = FairQueue()
        q.put(_Item("a", 0))
        assert q.get_nowait().tenant == "a"
        for i in range(10):
            q.put(_Item("b", i))
        q.put(_Item("a", 1))
        drained = [q.get_nowait() for _ in range(11)]
        a_pos = next(i for i, it in enumerate(drained) if it.tenant == "a")
        assert a_pos <= 2  # interleaved promptly, not first-by-credit

    def test_depths_snapshot(self):
        q = FairQueue()
        q.put(_Item("a", 0))
        q.put(_Item("a", 1))
        q.put(_Item(None, 0))
        assert q.depths() == {"a": 2, "(default)": 1}

    def test_unless_ends_an_empty_wait_and_an_item_comes_first(self):
        """ISSUE 38: `get(unless=)` raises Empty at once where the
        caller's other reason holds and nothing can be popped; an item
        that can be popped is returned whatever `unless` says."""
        q = FairQueue()
        with pytest.raises(stdlib_queue.Empty):
            q.get(timeout=30.0, unless=lambda: True)
        q.put(_Item("a", 0))
        assert q.get(timeout=30.0, unless=lambda: True).tenant == "a"
        # a skipped tenant's item is nothing to pop
        q.put(_Item("a", 1))
        with pytest.raises(stdlib_queue.Empty):
            q.get(timeout=30.0, skip={"a"}, unless=lambda: True)

    def test_wake_makes_a_blocked_get_read_unless_again(self):
        """A getter asleep with no timeout ends when what `unless` reads
        has changed and `wake()` was called after the change; a `wake()`
        with nothing changed leaves it asleep."""
        q = FairQueue()
        done = threading.Event()
        flag: list = []
        out: list = []

        def getter():
            try:
                out.append(q.get(unless=lambda: bool(flag)))
            except stdlib_queue.Empty:
                out.append("empty")
            done.set()

        t = threading.Thread(target=getter, daemon=True)
        t.start()
        time.sleep(0.05)
        q.wake()
        assert not done.wait(0.1)  # nothing changed: still asleep
        flag.append(1)
        q.wake()
        assert done.wait(5.0) and out == ["empty"]
        t.join(timeout=5.0)
        assert not t.is_alive()


# ---------------------------------------------------------------------------
# quotas
# ---------------------------------------------------------------------------


class _Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


class TestQuota:
    def test_token_bucket_refill_and_debt(self):
        clock = _Clock()
        b = TokenBucket(rate_per_s=2.0, burst=4.0, now_fn=clock)
        assert b.try_take(4.0) == 0.0  # burst available up front
        wait = b.try_take(1.0)
        assert wait == pytest.approx(0.5)  # 1 token / 2 per sec
        clock.t += 0.5
        assert b.try_take(1.0) == 0.0
        b.debit(3.0)  # post-paid: may go negative
        assert b.balance() < 0

    def test_qps_quota_admits_and_rejects(self):
        clock = _Clock()
        q = QuotaEnforcer(now_fn=clock)
        q.configure(Tenant(id="t", engine_id="e", qps=2.0))
        q.admit("t")
        q.admit("t")  # burst = max(qps, 1) = 2
        with pytest.raises(QuotaExceeded) as ei:
            q.admit("t")
        assert ei.value.resource == "qps"
        assert ei.value.retry_after_s > 0
        clock.t += 1.0  # refill 2 tokens
        q.admit("t")
        snap = q.snapshot("t")["t"]
        assert snap["admitted"] == 3
        assert snap["rejected"]["qps"] == 1

    def test_concurrency_quota_and_release(self):
        q = QuotaEnforcer(now_fn=_Clock())
        q.configure(Tenant(id="t", engine_id="e", max_concurrency=2))
        q.admit("t")
        q.admit("t")
        with pytest.raises(QuotaExceeded) as ei:
            q.admit("t")
        assert ei.value.resource == "concurrency"
        q.release("t")
        q.admit("t")  # slot freed

    def test_device_seconds_post_paid(self):
        clock = _Clock()
        q = QuotaEnforcer(now_fn=clock)
        q.configure(Tenant(id="t", engine_id="e", device_seconds_per_s=0.5))
        q.admit("t")  # bucket starts positive
        q.charge_device("t", 10.0)  # deep debt
        with pytest.raises(QuotaExceeded) as ei:
            q.admit("t")
        assert ei.value.resource == "device_seconds"
        clock.t += 30.0  # 15 device-seconds refilled > debt
        q.admit("t")
        assert q.snapshot("t")["t"]["device_seconds"] == pytest.approx(10.0)

    def test_unlimited_tenant_never_rejected(self):
        q = QuotaEnforcer(now_fn=_Clock())
        q.configure(Tenant(id="t", engine_id="e"))
        for _ in range(100):
            q.admit("t")

    def test_reconfigure_keeps_bucket_state(self):
        """A tenant refresh with unchanged rates must not refill a hog's
        spent bucket."""
        clock = _Clock()
        q = QuotaEnforcer(now_fn=clock)
        t = Tenant(id="t", engine_id="e", qps=1.0)
        q.configure(t)
        q.admit("t")
        q.configure(t)  # refresh tick
        with pytest.raises(QuotaExceeded):
            q.admit("t")


# ---------------------------------------------------------------------------
# model cache
# ---------------------------------------------------------------------------


class _FakeCacheTenant:
    def __init__(self, tid):
        self.id = tid


def _make_cache(capacity):
    loads = []
    cache = ModelCache(
        storage=None, capacity=capacity,
        build=lambda inst: f"runtime-{inst}",
    )
    cache.resolve_version = (  # type: ignore[method-assign]
        lambda tenant: (loads.append(tenant.id) or (f"v-{tenant.id}", tenant.id))
    )
    return cache, loads


class TestModelCache:
    def test_hit_miss_reload_accounting(self):
        cache, loads = _make_cache(capacity=1)
        t1, t2 = _FakeCacheTenant("t1"), _FakeCacheTenant("t2")
        e1 = cache.acquire(t1)
        cache.release(e1)
        cache.release(cache.acquire(t1))  # hit
        cache.release(cache.acquire(t2))  # miss → evicts t1 (capacity 1)
        cache.release(cache.acquire(t1))  # transparent reload
        s = cache.stats()
        assert s["hits"] == 1 and s["misses"] == 3
        assert s["reloads"] == 1 and s["evictions"] == 2
        assert loads == ["t1", "t2", "t1"]

    def test_lru_eviction_order(self):
        cache, _ = _make_cache(capacity=2)
        t = {k: _FakeCacheTenant(k) for k in ("a", "b", "c")}
        cache.release(cache.acquire(t["a"]))
        cache.release(cache.acquire(t["b"]))
        cache.release(cache.acquire(t["a"]))  # refresh a's recency
        cache.release(cache.acquire(t["c"]))  # evicts b (LRU), not a
        entries = cache.stats()["entries"]
        assert set(entries) == {"a", "c"}

    def test_inflight_runtime_never_evicted(self):
        cache, _ = _make_cache(capacity=1)
        t1, t2 = _FakeCacheTenant("t1"), _FakeCacheTenant("t2")
        lease = cache.acquire(t1)  # held: in-flight query
        cache.release(cache.acquire(t2))  # over capacity, t1 unevictable
        entries = cache.stats()["entries"]
        assert "t1" in entries  # survived, soft-over-capacity
        cache.release(lease)
        cache.release(cache.acquire(_FakeCacheTenant("t3")))
        assert "t1" not in cache.stats()["entries"]  # now evictable

    def test_pinned_runtime_never_evicted(self):
        cache, _ = _make_cache(capacity=1)
        t1, t2 = _FakeCacheTenant("t1"), _FakeCacheTenant("t2")
        cache.release(cache.acquire(t1))
        cache.pin("t1", on=True)
        cache.release(cache.acquire(t2))
        assert "t1" in cache.stats()["entries"]
        cache.pin("t1", on=False)
        cache.release(cache.acquire(_FakeCacheTenant("t3")))
        assert "t1" not in cache.stats()["entries"]

    def test_put_runtime_swaps_and_preserves_pin(self):
        cache, _ = _make_cache(capacity=2)
        t1 = _FakeCacheTenant("t1")
        cache.release(cache.acquire(t1))
        cache.pin("t1", on=True)
        cache.put_runtime("t1", "runtime-new", version_key="v-new")
        e = cache.stats()["entries"]["t1"]
        assert e["version"] == "v-new" and e["pinned"]
        assert cache.acquire(t1).runtime == "runtime-new"

    def test_load_failure_raises_model_load_error(self):
        cache = ModelCache(
            storage=None, capacity=1,
            build=lambda inst: (_ for _ in ()).throw(RuntimeError("boom")),
        )
        cache.resolve_version = lambda tenant: ("v", "inst")  # type: ignore
        with pytest.raises(ModelLoadError):
            cache.acquire(_FakeCacheTenant("t1"))

    def test_sync_prefetches_on_version_drift(self):
        versions = {"t1": "v1"}
        cache = ModelCache(
            storage=None, capacity=2,
            build=lambda inst: f"runtime-{inst}",
        )
        cache.resolve_version = (  # type: ignore[method-assign]
            lambda tenant: (versions[tenant.id], versions[tenant.id])
        )
        t1 = _FakeCacheTenant("t1")
        cache.release(cache.acquire(t1))
        assert cache.sync([t1]) == 0  # no drift
        versions["t1"] = "v2"  # a promote landed
        assert cache.sync([t1]) == 1
        entry = cache.acquire(t1)
        assert entry.runtime == "runtime-v2" and entry.version_key == "v2"
        assert cache.stats()["misses"] == 1  # the swap was not a miss


# ---------------------------------------------------------------------------
# tenant records
# ---------------------------------------------------------------------------


class TestTenantStore:
    def test_crud_roundtrip(self, fresh_storage):
        store = TenantStore(fresh_storage)
        t = store.upsert(Tenant(
            id="acme", engine_id="rec", weight=2.0, qps=100.0,
            description="the acme corp",
        ))
        assert t.engine_variant == "rec"  # defaulted
        got = store.get("acme")
        assert got.weight == 2.0 and got.qps == 100.0
        assert store.get("nope") is None
        store.upsert(Tenant(id="zeta", engine_id="rec"))
        assert [x.id for x in store.list()] == ["acme", "zeta"]
        assert store.delete("zeta") > 0
        assert store.get("zeta") is None

    def test_set_quota_updates_only_quota_fields(self, fresh_storage):
        store = TenantStore(fresh_storage)
        store.upsert(Tenant(id="acme", engine_id="rec", qps=10.0))
        t = store.set_quota("acme", qps=50.0, weight=3.0)
        assert t.qps == 50.0 and t.weight == 3.0
        assert store.get("acme").qps == 50.0
        with pytest.raises(KeyError):
            store.set_quota("nope", qps=1.0)
        with pytest.raises(ValueError):
            store.set_quota("acme", bogus=1.0)

    def test_validation(self):
        with pytest.raises(ValueError):
            Tenant(id="bad/id", engine_id="rec")  # slash breaks routing
        with pytest.raises(ValueError):
            Tenant(id="ok", engine_id="")
        with pytest.raises(ValueError):
            Tenant(id="ok", engine_id="rec", weight=0)
        t = Tenant(id="ok", engine_id="rec", qps=0)
        assert t.qps is None  # 0 means unlimited


# ---------------------------------------------------------------------------
# bounded tenant metric labels (cardinality guard)
# ---------------------------------------------------------------------------


def test_tenant_metric_labels_bounded(fresh_storage):
    from predictionio_tpu.tenancy.mux import OVERFLOW_LABEL, TenantMux

    mux = TenantMux(
        fresh_storage, metrics=MetricsRegistry(), cache_capacity=2,
        label_max=3,
    )
    labels = {mux.label(f"tenant-{i}") for i in range(50)}
    # 3 real labels + the shared overflow — a 50-tenant churn cannot
    # mint 50 metric children
    assert len(labels) == 4 and OVERFLOW_LABEL in labels
    # known labels stay stable
    assert mux.label("tenant-0") == "tenant-0"


# ---------------------------------------------------------------------------
# deleted-tenant cleanup + warm_and_pin (review hardening)
# ---------------------------------------------------------------------------


def test_deleted_tenant_releases_quota_and_cache(fresh_storage):
    from predictionio_tpu.tenancy.mux import TenantMux

    mux = TenantMux(
        fresh_storage, metrics=MetricsRegistry(), cache_capacity=2,
        refresh_s=0.0, sync_s=3600.0,
    )
    # fake-load a runtime so the cache holds state for the tenant
    mux.cache._build_fn = lambda inst: "rt"
    mux.cache.resolve_version = lambda tenant: ("v1", "inst")
    store = TenantStore(fresh_storage)
    store.upsert(Tenant(id="acme", engine_id="rec", qps=5.0))
    mux.refresh(force=True)
    mux.admit("acme")
    mux.done("acme", mux.cache.acquire(store.get("acme")))
    assert mux.quota.snapshot("acme")
    assert "acme" in mux.cache.stats()["entries"]

    store.delete("acme")
    mux.refresh(force=True)
    # quota buckets, cache entry, and host state all released — a
    # same-id recreate must not inherit the dead tenant's debt
    assert mux.quota.snapshot("acme") == {}
    assert "acme" not in mux.cache.stats()["entries"]
    with pytest.raises(Exception):
        mux.admit("acme")  # UnknownTenant


def test_warm_and_pin_leaves_entry_pinned():
    cache, _ = _make_cache(capacity=1)
    t1, t2 = _FakeCacheTenant("t1"), _FakeCacheTenant("t2")
    cache.warm_and_pin(t1)
    e = cache.stats()["entries"]["t1"]
    assert e["pinned"] and e["refs"] == 0
    # pinned with zero refs: survives capacity pressure immediately —
    # the window between warm and a later pin() call is gone
    cache.release(cache.acquire(t2))
    assert "t1" in cache.stats()["entries"]


def test_resume_latch_survives_failed_first_refresh(fresh_storage):
    """A storage blip during the first sync pass must not consume the
    one-shot rollout re-adoption: the latch is only set after a clean
    pass over a SUCCESSFUL refresh, and a raising per-tenant resume
    keeps it open for the next pass."""
    from predictionio_tpu.tenancy.mux import TenantMux

    mux = TenantMux(
        fresh_storage, metrics=MetricsRegistry(), cache_capacity=2,
        refresh_s=0.0, sync_s=3600.0,
    )
    store = TenantStore(fresh_storage)
    store.upsert(Tenant(id="acme", engine_id="rec"))

    def _down():
        raise RuntimeError("storage down")

    orig_list = mux.store.list
    mux.store.list = _down
    mux.sync()
    assert not mux._resumed, "failed refresh consumed the re-adoption"
    mux.store.list = orig_list

    calls: list = []

    def _boom(t):
        calls.append(t.id)
        raise RuntimeError("transient resume failure")

    mux._resume_rollout = _boom
    mux.sync()
    assert calls == ["acme"]
    assert not mux._resumed, "failed per-tenant resume latched anyway"

    mux._resume_rollout = lambda t: calls.append(f"ok:{t.id}")
    mux.sync()
    assert mux._resumed and calls[-1] == "ok:acme"


def test_resume_gives_up_after_repeated_failures(fresh_storage):
    """A PERMANENTLY unservable baseline (blob GC'd, instance purged)
    must not keep the resume pass — record folds plus a failing model
    build — churning every sync for the life of the process: after 3
    consecutive failures the tenant is skipped and the latch sets."""
    from predictionio_tpu.tenancy.mux import TenantMux

    mux = TenantMux(
        fresh_storage, metrics=MetricsRegistry(), cache_capacity=2,
        refresh_s=0.0, sync_s=3600.0,
    )
    store = TenantStore(fresh_storage)
    store.upsert(Tenant(id="acme", engine_id="rec"))
    calls: list = []

    def _boom(t):
        calls.append(t.id)
        raise RuntimeError("baseline unservable")

    mux._resume_rollout = _boom
    for _ in range(5):
        mux.sync()
    assert len(calls) == 3, "give-up cap did not bound the retries"
    assert mux._resumed, "latch never set after the give-up"


def test_stop_freezes_cache_gauges_and_releases_mux(fresh_storage):
    """stop() must replace the registry's gauge closures (they close
    over the mux) with constants: otherwise the process-global registry
    keeps the dead mux — and every resident runtime in its cache —
    alive for the rest of the process."""
    import gc
    import weakref

    from predictionio_tpu.tenancy.mux import TenantMux

    reg = MetricsRegistry()
    mux = TenantMux(
        fresh_storage, metrics=reg, cache_capacity=2,
        refresh_s=0.0, sync_s=3600.0,
    )
    mux.cache._build_fn = lambda inst: "rt"
    mux.cache.resolve_version = lambda tenant: ("v1", "inst")
    store = TenantStore(fresh_storage)
    store.upsert(Tenant(id="acme", engine_id="rec"))
    mux.refresh(force=True)
    mux.cache.release(mux.cache.acquire(store.get("acme")))
    mux.stop()

    ref = weakref.ref(mux.cache)
    del mux
    gc.collect()
    assert ref() is None, (
        "registry gauge closure kept the dead mux's cache alive"
    )
    # /metrics still renders the frozen final values
    assert "tenant_cache_resident 1" in reg.render()
