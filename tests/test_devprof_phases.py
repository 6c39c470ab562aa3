"""`device.launch` / `device.wait` (ISSUE 37): the device profiler's bracket
around an instrumented program, split at the one more clock read — as spans
and as `launch_seconds` / `wait_seconds` beside `device_seconds`."""

from __future__ import annotations

import json
import time
import urllib.request

import jax
import jax.numpy as jnp
import pytest

import predictionio_tpu.obs.spans as spans
from predictionio_tpu.obs import devprof


@pytest.fixture()
def phases():
    """Every `device.launch` / `device.wait` recorded while the test runs
    (a bridge sees the state spans of a call under no span, too)."""
    rec = spans.get_default_recorder()
    seen: list = []
    for name in ("device.launch", "device.wait"):
        rec.bridge(name, seen.append)
    yield seen
    for name in ("device.launch", "device.wait"):
        rec.unbridge(name)


def _program(name):
    return devprof.instrument(name, jax.jit(lambda x: (x @ x.T).sum()))


def test_a_call_yields_launch_then_wait_within_device_seconds(phases):
    name = f"t.phases.{time.monotonic_ns()}"
    fn, x = _program(name), jnp.ones((64, 64))
    fn(x)  # the first call compiles: its compile is taken out of the record
    del phases[:]
    before = devprof.get_profiler().executable(name)
    with spans.span("outer") as outer:
        fn(x)
    after = devprof.get_profiler().executable(name)
    assert [s.name for s in phases] == ["device.launch", "device.wait"]
    launch, wait = phases
    assert launch.attrs["program"] == wait.attrs["program"] == name
    assert launch.parent_span_id == wait.parent_span_id == outer.span_id
    assert launch.trace_id == outer.trace_id
    assert launch.start_mono + launch.duration <= wait.start_mono + 1e-6
    spent = after["device_seconds"] - before["device_seconds"]
    assert 0.0 < launch.duration + wait.duration <= spent + 2e-6
    # the same two intervals on the executable's record: the sum holds
    assert after["invocations"] == before["invocations"] + 1
    assert after["launch_seconds"] + after["wait_seconds"] == pytest.approx(
        after["device_seconds"], abs=2e-6)
    assert after["launch_seconds"] > before["launch_seconds"]
    assert after["wait_seconds"] >= before["wait_seconds"]


def test_a_call_under_no_span_roots_no_trace(phases):
    """A warm-up, a bare script: the two phases are state spans — in
    stats(), in no trace — so no program call finalizes a trace of its own."""
    rec = spans.get_default_recorder()
    name = f"t.bare.{time.monotonic_ns()}"
    fn, x = _program(name), jnp.ones((8, 8))
    t0 = time.monotonic()
    fn(x)
    fn(x)
    assert [s.name for s in phases] == ["device.launch", "device.wait"] * 2
    assert {s.trace_id for s in phases} == {spans.NO_TRACE}
    assert {s.attrs["program"] for s in phases} == {name}
    stats = rec.stats(t0 - 1.0)
    assert stats["device.launch"]["count"] >= 2
    assert stats["device.wait"]["count"] >= 2
    mine = {s.span_id for s in phases}
    assert not mine & {s.span_id for s in rec.recent()}


def test_a_passthrough_yields_nothing(phases, monkeypatch):
    """PIO_DEVPROF=0 and a call inside an outer jit pass straight through:
    no span, no record."""
    name = f"t.off.{time.monotonic_ns()}"
    fn, x = _program(name), jnp.ones((8, 8))
    monkeypatch.setenv("PIO_DEVPROF", "0")
    fn(x)
    assert phases == []
    assert devprof.get_profiler().executable(name) is None
    monkeypatch.delenv("PIO_DEVPROF")
    outer = jax.jit(lambda v: fn(v) + 1.0)
    outer(x)  # traces through the wrapper: nested dispatch, nothing timed
    outer(x)
    assert phases == []
    assert devprof.get_profiler().executable(name) is None
    fn(x)
    assert [s.name for s in phases] == ["device.launch", "device.wait"]


def test_a_failing_call_marks_its_launch_and_propagates(phases):
    def boom(x):
        raise ValueError("no such program")

    fn = devprof.instrument(f"t.boom.{time.monotonic_ns()}", boom)
    with pytest.raises(ValueError):
        fn(jnp.ones((2,)))
    assert [(s.name, s.error) for s in phases] == [("device.launch", True)]


def test_record_external_counts_as_wait():
    name = f"t.ext.{time.monotonic_ns()}"
    devprof.get_profiler().record_external(name, 0.25, 3)
    row = devprof.get_profiler().executable(name)
    assert row["device_seconds"] == row["wait_seconds"] == 0.25
    assert row["launch_seconds"] == 0.0 and row["invocations"] == 3


def test_debug_profile_shows_the_split():
    """`GET /debug/profile`: `launch_seconds` and `wait_seconds` beside every
    executable's `device_seconds`, the existing fields as they were."""
    from predictionio_tpu.workflow import server as S
    from test_dispatch_spans import _runtime

    name = f"t.http.{time.monotonic_ns()}"
    fn = _program(name)
    fn(jnp.ones((8, 8)))
    fn(jnp.ones((8, 8)))
    srv = S.QueryServer(None, _runtime(),
                        S.QueryServerConfig(ip="127.0.0.1", port=0))
    port = srv.start()
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/debug/profile", timeout=30) as r:
            report = json.loads(r.read())
    finally:
        srv.stop()
    row = next(e for e in report["executables"] if e["name"] == name)
    assert {"name", "static_kwargs", "signatures", "invocations",
            "compile_seconds", "device_seconds", "launch_seconds",
            "wait_seconds", "flops_per_call", "flops_total"} <= set(row)
    assert row["invocations"] == 2
    assert row["launch_seconds"] + row["wait_seconds"] == pytest.approx(
        row["device_seconds"], abs=3e-6)
    assert {"platform", "executables", "totals", "padding"} <= set(report)
