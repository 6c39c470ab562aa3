"""JAX runtime gauges: jit compile activity + live device buffers.

Compile events come from jax's monitoring hooks (a process-global
duration listener accumulates `/jax/core/compile/*` events — notably
`backend_compile_duration`, one per XLA compile). Live-buffer gauges are
callback gauges sampled at scrape time via `jax.live_arrays()`, so a
`GET /metrics` shows the device-memory footprint *now*, not at some
earlier sampling tick. A process that never imported jax reads every
gauge as 0 and never pays the import."""

from __future__ import annotations

import threading

from predictionio_tpu.obs.registry import MetricsRegistry

_lock = threading.Lock()
_compile_count = 0
_compile_seconds = 0.0
_listener_installed = False


def _on_duration(event: str, duration: float, **_kw) -> None:
    global _compile_count, _compile_seconds
    if "/jax/core/compile" not in event:
        return
    with _lock:
        _compile_seconds += duration
        if event.endswith("backend_compile_duration"):
            _compile_count += 1


def ensure_compile_listener() -> None:
    """Hook jax's monitoring events (idempotent). Importing jax costs
    ~2 s, so ONLY call this from paths that are jax-bound anyway — the
    train workflow and deploy-runtime construction call it before their
    first compile; data-plane processes (event server, storage daemon,
    dashboard) never pay the import and read compile gauges as 0."""
    global _listener_installed
    with _lock:
        if _listener_installed:
            return
        _listener_installed = True
    import jax.monitoring

    jax.monitoring.register_event_duration_secs_listener(_on_duration)


def compile_snapshot() -> tuple[int, float]:
    """(compiles seen, seconds spent) so far — train-stage spans diff
    this across a stage to attribute XLA compile time to the stage that
    paid it."""
    with _lock:
        return _compile_count, _compile_seconds


def _rearm_if_jax_loaded() -> None:
    """Late-import gap fix: a process that wires /metrics BEFORE its
    first jax import used to scrape compile gauges stuck at 0 forever
    (install_jax_gauges only armed the listener if jax was already in
    sys.modules). Re-checking at scrape time arms the listener the first
    time a scrape observes jax loaded — compiles before that scrape are
    missed, every one after is counted. Still never IMPORTS jax."""
    import sys

    if "jax" in sys.modules and not _listener_installed:
        ensure_compile_listener()


def _compile_count_now() -> float:
    _rearm_if_jax_loaded()
    with _lock:
        return float(_compile_count)


def _compile_seconds_now() -> float:
    _rearm_if_jax_loaded()
    with _lock:
        return _compile_seconds


def _live_arrays() -> list:
    import sys

    if "jax" not in sys.modules:
        # data-plane processes (event server, storage daemon, dashboard)
        # must not pay the multi-second jax import on their first scrape;
        # no jax loaded ⇒ no live buffers, truthfully
        return []
    import jax

    return list(jax.live_arrays())


def install_jax_gauges(registry: MetricsRegistry) -> None:
    """Register the JAX runtime gauges on `registry` (idempotent)."""
    import sys

    if "jax" in sys.modules:  # hook compiles, but never IMPORT jax here
        ensure_compile_listener()
    registry.gauge_callback(
        "jax_jit_compile_count",
        "XLA backend compiles observed in this process",
        _compile_count_now,
    )
    registry.gauge_callback(
        "jax_jit_compile_seconds_total",
        "seconds spent in jax trace/lower/compile in this process",
        _compile_seconds_now,
    )
    registry.gauge_callback(
        "jax_live_buffer_count",
        "live jax arrays (sampled at scrape)",
        lambda: float(len(_live_arrays())),
    )
    registry.gauge_callback(
        "jax_live_buffer_bytes",
        "bytes held by live jax arrays (sampled at scrape)",
        lambda: float(
            sum(a.nbytes for a in _live_arrays())
        ),
    )
