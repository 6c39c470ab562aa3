"""Readers of the serving path's split spans (ISSUE 37; a metric's own file
under layer_metrics/ names its span, the arithmetic is here once): the
device-wait span divided into the puts, the device profiler's
`device.launch` / `device.wait` and the copies back, the request's closure,
the dispatcher's measured share of the window with no query to serve.

All of it reads `predictionio_tpu/obs/spans.py`' windowed statistics through
`span_metrics.window_stats`, so a program without these spans (the parent
commit) reads as None and the metric is left out of the line.
"""

from __future__ import annotations

import math

import numpy as np

from benchmarks import trace_reduce, ur_metrics
from benchmarks.span_metrics import mean_ms, window_stats

BATCH = "batch.predict"  # one a batch, on the dispatcher's worker thread
#: what a request's spans name of it, besides its own self time
REQUEST_PARTS = ("query.decode", "query.encode", "batch.queue_wait",
                 "batch.device_dispatch", "batch.result_transfer",
                 "query.wake")


def per_batch_ms(reading, *names, field="total_s"):
    """Milliseconds a batch of the spans `names` together (those the cell
    has), over the measured window: their seconds over the window's count of
    `batch.predict`. None where the window holds none of them."""
    batches = window_stats(reading, BATCH)
    rows = [r for r in (window_stats(reading, n) for n in names) if r]
    if not batches or not batches["count"] or not rows:
        return None
    return 1000.0 * sum(r[field] for r in rows) / batches["count"]


def request_unattributed_ms(reading):
    """Mean `server.request` less its self time and the means of
    `REQUEST_PARTS`, each over the measured window's queries. The new span
    of the set, `query.wake`, has to be there: without it the remainder is
    the parent's, which this metric does not describe."""
    whole = mean_ms(reading, "server.request")
    own = mean_ms(reading, "server.request", field="self_s")
    parts = [mean_ms(reading, n) for n in REQUEST_PARTS]
    if whole is None or own is None or any(p is None for p in parts):
        return None
    return whole - own - sum(parts)


def outside_server_ms(reading):
    """Mean latency of the window's answered queries at the generator less
    the mean `server.request`."""
    inside = mean_ms(reading, "server.request")
    lat, ok = reading.window.get("latencies_ms"), reading.window.get("ok")
    if inside is None or lat is None or ok is None:
        return None
    answered = np.asarray(lat, float)[np.asarray(ok, bool)]
    if not answered.size:
        return None
    return float(answered.mean()) - inside


def no_work_pct(reading):
    """`dispatch.no_work` seconds over the whole seconds the statistics were
    summed over (they are kept a second a bucket: the window's edges are
    taken to the whole second, the stretch's pieces are at most 0.2 s)."""
    row = window_stats(reading, "dispatch.no_work")
    window = reading.window.get("measured_monotonic")
    if row is None or window is None:
        return None
    seconds = math.ceil(window[1]) - math.floor(window[0])
    return 100.0 * row["total_s"] / seconds


def idle_pct(reading):
    """The cell's own `device.idle_pct.*` reading, by that metric's reader:
    `ur_metrics.idle_pct` where the Universal Recommender's program ran (it
    scales by the share of the window the trace covers), else
    `trace_reduce.idle_pct`."""
    if reading.trace is None:
        return None
    if ur_metrics.covered_share(reading) is not None:
        return ur_metrics.idle_pct(reading)
    return trace_reduce.idle_pct(reading)


def idle_with_work_pct(reading):
    idle, no_work = idle_pct(reading), no_work_pct(reading)
    if idle is None or no_work is None:
        return None
    return idle - no_work
