"""Device-profiling layer (ISSUE 3): executable registry, degradation
contract, padding-waste accounting, /debug/profile + capture endpoints,
and the query-server acceptance path (batched queries → nonzero flops,
MFU in (0, 1], padding histogram with samples)."""

import json
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

from predictionio_tpu.obs import devprof
from predictionio_tpu.obs.registry import MetricsRegistry


@pytest.fixture(autouse=True)
def _fresh_profiler():
    devprof.get_profiler().clear()
    yield
    devprof.get_profiler().clear()


# ---------------------------------------------------------------------------
# degradation contract — profiling must never break the caller
# ---------------------------------------------------------------------------


class _FakeJit:
    """Duck-typed 'jitted' callable whose AOT surface misbehaves."""

    def __init__(self, result=42.0, lower_raises=False, cost_raises=False):
        self.result = result
        self.lower_raises = lower_raises
        self.cost_raises = cost_raises
        self.calls = 0

    def __call__(self, *args, **kwargs):
        self.calls += 1
        return self.result

    def lower(self, *args, **kwargs):
        if self.lower_raises:
            raise RuntimeError("private API moved")
        outer = self

        class _Lowered:
            def cost_analysis(self):
                if outer.cost_raises:
                    raise RuntimeError("cost_analysis drifted")
                return {"flops": 123.0, "bytes accessed": 456.0}

            def compile(self):
                raise RuntimeError("no backend here")

        return _Lowered()


def test_no_lower_attribute_degrades_to_zero_analysis():
    fn = lambda x: x + 1  # plain callable: no .lower at all
    wrapped = devprof.instrument("t.nolower", fn)
    assert wrapped(2) == 3
    prof = devprof.get_profiler().executable("t.nolower")
    assert prof is not None
    assert prof["invocations"] == 1
    assert prof["flops_total"] == 0.0
    assert prof["cost_analysis_ok"] is False


def test_lower_raising_counts_invocations_without_flops():
    fake = _FakeJit(lower_raises=True)
    wrapped = devprof.instrument("t.lowerfail", fake)
    for _ in range(3):
        assert wrapped(1.0) == 42.0
    prof = devprof.get_profiler().executable("t.lowerfail")
    assert fake.calls == 3
    assert prof["invocations"] == 3
    assert prof["flops_total"] == 0.0


def test_cost_analysis_raising_degrades_but_still_counts():
    fake = _FakeJit(cost_raises=True)
    wrapped = devprof.instrument("t.costfail", fake)
    wrapped(1.0)
    prof = devprof.get_profiler().executable("t.costfail")
    assert prof["invocations"] == 1
    assert prof["cost_analysis_ok"] is False
    assert prof["flops_total"] == 0.0
    # memory path failing (compile raises) must not poison anything
    wrapped_m = devprof.instrument("t.memfail", _FakeJit(), memory=True)
    wrapped_m(1.0)
    prof = devprof.get_profiler().executable("t.memfail")
    assert prof["flops_total"] == 123.0
    assert prof["memory_analysis_ok"] is False


def test_wrapped_function_exception_propagates_once():
    calls = {"n": 0}

    def boom(x):
        calls["n"] += 1
        raise ValueError("query-level contract violation")

    wrapped = devprof.instrument("t.boom", boom)
    with pytest.raises(ValueError):
        wrapped(1)
    assert calls["n"] == 1  # never re-executed by profiler bookkeeping


def test_failed_first_call_does_not_poison_signature():
    """A raising first call must release its reserved analysis slot so a
    later successful call still gets analyzed."""
    state = {"fail": True}
    inner = _FakeJit()

    def flaky(*args, **kwargs):
        if state["fail"]:
            raise RuntimeError("transient")
        return inner(*args, **kwargs)

    flaky.lower = inner.lower
    wrapped = devprof.instrument("t.flaky", flaky)
    with pytest.raises(RuntimeError):
        wrapped(1.0)
    state["fail"] = False
    wrapped(1.0)
    prof = devprof.get_profiler().executable("t.flaky")
    assert prof["invocations"] == 1  # the failed call never accounted
    assert prof["flops_total"] == 123.0  # ...and analysis still ran


def test_disabled_via_env_is_pure_passthrough(monkeypatch):
    monkeypatch.setenv("PIO_DEVPROF", "0")
    wrapped = devprof.instrument("t.disabled", _FakeJit())
    wrapped(1.0)
    assert devprof.get_profiler().executable("t.disabled") is None


def test_jax_absent_passthrough_and_empty_report(monkeypatch):
    import sys

    monkeypatch.delitem(sys.modules, "jax", raising=False)
    fake = _FakeJit()
    wrapped = devprof.instrument("t.nojax", fake)
    assert wrapped(1.0) == 42.0
    assert fake.calls == 1
    # nothing recorded — the wrapper never engaged
    assert devprof.get_profiler().executable("t.nojax") is None
    rep = devprof.report()
    assert rep["executables"] == []
    assert rep["platform"]["platform"] is None
    assert rep["totals"]["invocations"] == 0


def test_platform_missing_from_peak_table_yields_no_mfu(monkeypatch):
    monkeypatch.setattr(devprof, "PEAK_TABLE", {})
    monkeypatch.delenv("PIO_PEAK_FLOPS", raising=False)
    monkeypatch.delenv("PIO_PEAK_HBM_BPS", raising=False)
    info = devprof.platform_info()
    assert info["peak_flops"] is None
    assert info["peak_source"] == "none"
    assert devprof.mfu(1e9, 1.0) is None
    fake = _FakeJit()
    wrapped = devprof.instrument("t.nopeak", fake)
    wrapped(1.0)
    prof = devprof.get_profiler().executable("t.nopeak")
    assert prof["invocations"] == 1
    assert "mfu" not in prof  # derived fields absent, not wrong


def test_unknown_device_kind_has_no_peaks(monkeypatch):
    """PR 21: peaks come from the device_kind row or an env pin, never
    from the platform name — the CPU (or any kind the table lacks)
    yields None peaks and `peak_source == "none"`."""
    jax = pytest.importorskip("jax")
    monkeypatch.delenv("PIO_PEAK_FLOPS", raising=False)
    monkeypatch.delenv("PIO_PEAK_HBM_BPS", raising=False)
    info = devprof.platform_info()
    assert info["platform"] == jax.devices()[0].platform == "cpu"
    assert info["device_kind"] == jax.devices()[0].device_kind
    assert info["device_count"] == len(jax.devices())
    assert info["peak_flops"] is None and info["peak_hbm_bps"] is None
    assert info["peak_source"] == "none"
    assert devprof.platform_info("int8")["peak_flops"] is None
    assert devprof.mfu(1e9, 1.0) is None
    assert devprof.hbm_fraction(1e9, 1.0) is None


def test_env_peak_override(monkeypatch):
    monkeypatch.setenv("PIO_PEAK_FLOPS", "1e12")
    monkeypatch.setenv("PIO_PEAK_HBM_BPS", "1e11")
    info = devprof.platform_info()
    assert info["peak_flops"] == 1e12
    assert info["peak_source"] == "env"
    assert devprof.mfu(5e11, 1.0) == 0.5
    assert devprof.hbm_fraction(5e10, 1.0) == 0.5
    # clamped at 1.0
    assert devprof.mfu(5e13, 1.0) == 1.0


# ---------------------------------------------------------------------------
# real jit integration
# ---------------------------------------------------------------------------


def test_real_jit_cost_memory_and_scale(monkeypatch):
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    # the CPU has no row in the peak table (PR 21: no made-up peaks), so
    # the derived roofline fields are exercised against operator pins
    monkeypatch.setenv("PIO_PEAK_FLOPS", "2e11")
    monkeypatch.setenv("PIO_PEAK_HBM_BPS", "5e10")

    @jax.jit
    def mm(a, b):
        return a @ b

    wrapped = devprof.instrument("t.matmul", mm, memory=True)
    x = np.ones((64, 64), np.float32)
    for _ in range(4):
        wrapped(x, x)
    prof = devprof.get_profiler().executable("t.matmul")
    assert prof["invocations"] == 4
    assert prof["signatures"] == 1
    assert prof["cost_analysis_ok"]
    # 2*64^3 = 524288 flops per call
    assert prof["flops_per_call"] == pytest.approx(2 * 64**3, rel=0.05)
    assert prof["flops_total"] == pytest.approx(4 * 2 * 64**3, rel=0.05)
    assert prof["memory_analysis_ok"]
    assert prof["argument_bytes"] == 2 * 64 * 64 * 4
    assert prof["output_bytes"] == 64 * 64 * 4
    assert prof["device_seconds"] > 0
    assert 0 < prof["mfu"] <= 1.0
    # second shape → second signature
    y = np.ones((32, 32), np.float32)
    wrapped(y, y)
    prof = devprof.get_profiler().executable("t.matmul")
    assert prof["signatures"] == 2

    # scale_by: static-kwarg loop correction multiplies per-call flops
    from functools import partial

    @partial(jax.jit, static_argnames=("iterations",))
    def loopy(a, *, iterations):
        return jax.lax.fori_loop(0, iterations, lambda i, c: c @ a, a)

    w2 = devprof.instrument("t.loopy", loopy, scale_by="iterations")
    w2(x, iterations=7)
    prof = devprof.get_profiler().executable("t.loopy")
    assert prof["flops_scaled_by"] == "iterations"
    assert prof["flops_total"] == pytest.approx(7 * prof["flops_per_call"])

    # attribute access forwards to the wrapped jit (AOT surface intact)
    assert hasattr(wrapped, "lower")
    snap = devprof.snapshot()
    assert snap.invocations == 6
    assert snap.flops > 0


def test_nested_dispatch_passes_through_untimed():
    jax = pytest.importorskip("jax")

    inner = devprof.instrument("t.inner", jax.jit(lambda a: a * 2))

    @jax.jit
    def outer(a):
        return inner(a) + 1

    out = outer(np.ones(4, np.float32))
    np.testing.assert_allclose(np.asarray(out), 3.0)
    # the traced call must NOT have recorded (timing tracers is bogus)
    assert devprof.get_profiler().executable("t.inner") is None
    # a top-level dispatch of the same wrapper records normally
    inner(np.ones(4, np.float32))
    assert devprof.get_profiler().executable("t.inner")["invocations"] == 1


# ---------------------------------------------------------------------------
# padding accounting
# ---------------------------------------------------------------------------


def test_record_batch_padding_and_summary():
    reg = MetricsRegistry()
    devprof.record_batch_padding(5, 8, flops=8000.0, registry=reg)
    devprof.record_batch_padding(8, 8, flops=1000.0, registry=reg)
    s = devprof.padding_summary(registry=reg)
    assert s["batches"] == 2
    assert s["rows_real"] == 13
    assert s["rows_padded"] == 16
    # only the padded batch wastes: 8000 * 3/8 = 3000
    assert s["wasted_flops"] == pytest.approx(3000.0)
    assert 0 < s["mean_padding_ratio"] < 0.375 + 1e-9
    # degenerate inputs are inert
    devprof.record_batch_padding(3, 0, registry=reg)
    devprof.record_batch_padding(10, 8, flops=100.0, registry=reg)  # clamped
    s = devprof.padding_summary(registry=reg)
    assert s["batches"] == 3
    assert s["wasted_flops"] == pytest.approx(3000.0)


def test_external_seconds_attribution():
    devprof.get_profiler().record_external("t.dispatcher", 0.25, 3)
    prof = devprof.get_profiler().executable("t.dispatcher")
    assert prof["device_seconds"] == pytest.approx(0.25)
    assert prof["invocations"] == 3


# ---------------------------------------------------------------------------
# gauges + report shape
# ---------------------------------------------------------------------------


def test_devprof_gauges_render_on_registry():
    jax = pytest.importorskip("jax")

    wrapped = devprof.instrument("t.gauge", jax.jit(lambda a: a + 1))
    wrapped(np.ones((8, 8), np.float32))
    reg = MetricsRegistry()
    devprof.install_devprof_gauges(reg)
    text = reg.render()
    assert "devprof_executables 1" in text
    assert "devprof_invocations_total 1" in text
    assert "devprof_device_seconds_total" in text
    rep = devprof.report()
    assert rep["totals"]["invocations"] == 1
    assert rep["executables"][0]["name"] == "t.gauge"


def test_capture_requires_jax_and_serializes(monkeypatch, tmp_path):
    import sys

    with pytest.raises(ValueError):
        devprof.capture_trace(str(tmp_path), 0.0)
    monkeypatch.delitem(sys.modules, "jax", raising=False)
    with pytest.raises(RuntimeError, match="jax is not loaded"):
        devprof.capture_trace(str(tmp_path), 0.5)


# ---------------------------------------------------------------------------
# acceptance e2e: query server → /debug/profile
# ---------------------------------------------------------------------------


@pytest.fixture
def mem_storage():
    from predictionio_tpu.data.storage.registry import (
        SourceConfig,
        Storage,
        StorageConfig,
    )

    return Storage(StorageConfig(
        sources={"MEM": SourceConfig("MEM", "memory", {})},
        repositories={
            "METADATA": "MEM", "EVENTDATA": "MEM", "MODELDATA": "MEM",
        },
    ))


def _get_json(port, path):
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=10
    ) as r:
        return json.loads(r.read().decode())


def test_query_server_debug_profile_acceptance(mem_storage, monkeypatch):
    """The ISSUE 3 acceptance criterion: after a round of batched
    queries, GET /debug/profile reports ≥1 executable with nonzero
    flops, a derived MFU in (0, 1], and a batch_padding_ratio histogram
    with samples."""
    pytest.importorskip("jax")
    from predictionio_tpu.data.event import Event
    from predictionio_tpu.data.storage.base import App
    from predictionio_tpu.workflow.core import run_train
    from predictionio_tpu.workflow.server import (
        QueryServer,
        QueryServerConfig,
        latest_completed_runtime,
    )

    app_id = mem_storage.get_meta_data_apps().insert(App(0, "profapp"))
    events = mem_storage.get_events()
    events.init_app(app_id)
    rng = np.random.RandomState(0)
    batch = [
        Event(
            event="rate", entity_type="user", entity_id=f"u{u}",
            target_entity_type="item", target_entity_id=f"i{rng.randint(20)}",
            properties={"rating": float(rng.randint(1, 6))},
        )
        for u in range(12) for _ in range(15)
    ]
    events.insert_batch(batch, app_id)
    variant = {
        "id": "profrec",
        "engineFactory":
            "predictionio_tpu.engines.recommendation.RecommendationEngine",
        "datasource": {"params": {"app_name": "profapp"}},
        "algorithms": [
            {"name": "als", "params": {"rank": 4, "num_iterations": 3}}
        ],
    }
    # a derived MFU needs a peak; the CPU test platform has none in the
    # table (PR 21), so pin one the way an operator would
    monkeypatch.setenv("PIO_PEAK_FLOPS", "2e11")
    monkeypatch.setenv("PIO_PEAK_HBM_BPS", "5e10")
    run_train(mem_storage, variant)
    runtime = latest_completed_runtime(mem_storage, "profrec", "0", "profrec")
    srv = QueryServer(
        mem_storage, runtime, QueryServerConfig(ip="127.0.0.1", port=0)
    )
    port = srv.start()
    try:
        # a round of concurrent queries so the dispatcher coalesces
        def post_one(u):
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/queries.json",
                data=json.dumps({"user": f"u{u}", "num": 5}).encode(),
                headers={"Content-Type": "application/json"},
                method="POST",
            )
            urllib.request.urlopen(req, timeout=30).read()

        threads = [
            threading.Thread(target=post_one, args=(u % 12,))
            for u in range(16)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()

        rep = _get_json(port, "/debug/profile")
        execs = [e for e in rep["executables"] if e["flops_total"] > 0]
        assert execs, "no executable with nonzero flops on /debug/profile"
        with_mfu = [e for e in execs if "mfu" in e]
        assert with_mfu, "no executable derived an MFU"
        for e in with_mfu:
            assert 0 < e["mfu"] <= 1.0
        assert rep["padding"]["batches"] > 0
        assert rep["padding"]["rows_padded"] >= rep["padding"]["rows_real"]

        # padding histogram also rides /metrics (merged default registry)
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10
        ) as r:
            text = r.read().decode()
        assert "batch_padding_ratio_count" in text
        assert "devprof_invocations_total" in text

        # capture endpoint is guarded: no PIO_PROFILE_CAPTURE_DIR → 403
        monkeypatch.delenv("PIO_PROFILE_CAPTURE_DIR", raising=False)
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/debug/profile/capture",
            data=b"{}", headers={"Content-Type": "application/json"},
            method="POST",
        )
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(req, timeout=10)
        assert exc.value.code == 403
    finally:
        srv.stop()


def test_debug_profile_on_data_plane_server(mem_storage):
    """A server in a process that (notionally) never ran device work
    still serves a valid, possibly-empty profile — never a 500."""
    from predictionio_tpu.tools.admin import AdminServer

    devprof.get_profiler().clear()
    srv = AdminServer(mem_storage, ip="127.0.0.1", port=0)
    srv.start()
    try:
        rep = _get_json(srv.port, "/debug/profile")
        assert "executables" in rep and "platform" in rep
        assert rep["totals"]["invocations"] == 0
    finally:
        srv.stop()
