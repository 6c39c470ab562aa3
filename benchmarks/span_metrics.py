"""Readers of the program's own spans (`predictionio_tpu/obs/spans.py`), for
the per-layer metrics that time a host layer from the inside (a metric's own
file under layer_metrics/ names its span; the arithmetic is here once).

Train cells: `run_train` writes the seconds of every span that completed
under a job into EngineInstance.env["stage_timings"], which the driver hands
through whole. Serving cells: the server runs in the benchmark's process, so
the process's span recorder is asked for its statistics over the measured
window (both are on `time.monotonic()`). A program that has no such span, or
a recorder that keeps no statistics, reads as None: the metric is left out.
"""

from __future__ import annotations


def job_seconds(reading, *names):
    """Mean over the window's jobs of the seconds the spans `names` took
    together in a job; a job that recorded none of them does not count."""
    totals = [
        sum(t.get(n, 0.0) for n in names)
        for t in (j.get("stage_timings", {})
                  for j in reading.window.get("jobs", []))
        if any(n in t for n in names)
    ]
    if not totals:
        return None
    return sum(totals) / len(totals)


def job_unattributed_seconds(reading):
    """Mean over the window's jobs of what no span of the job names: the
    driver's wall time around `run_train` minus the job's root span, plus
    the part of the root span that none of its leaf spans covers."""
    gaps = [
        j["seconds"] - j["stage_timings"]["job"]
        + j["stage_timings"]["unattributed"]
        for j in reading.window.get("jobs", [])
        if {"job", "unattributed"} <= set(j.get("stage_timings", {}))
    ]
    if not gaps:
        return None
    return sum(gaps) / len(gaps)


def window_stats(reading, name):
    """{"count", "total_s", "self_s"} of the spans `name` that ended in the
    measured window, or None."""
    window = reading.window.get("measured_monotonic")
    if window is None:
        return None
    from predictionio_tpu.obs.spans import get_default_recorder

    stats = getattr(get_default_recorder(), "stats", None)
    if stats is None:
        return None
    return stats(*window).get(name)


def mean_ms(reading, name, field="total_s"):
    """Milliseconds per span `name` over the measured window."""
    row = window_stats(reading, name)
    if not row or not row["count"]:
        return None
    return 1000.0 * row[field] / row["count"]
