"""Driver `http_open_loop`: the client's wait. Poisson arrivals at the
cell's fixed `rate_qps` from generator processes of their own; every query
is timed from the instant it was DUE on the schedule to the last byte of
its reply, and the percentiles are over ALL queries of the window (one that
failed counts as slower than any reply)."""

from __future__ import annotations

from benchmarks import serving
from benchmarks.harness import Context


rehearsal_env = serving.rehearsal_env
setup = serving.setup


def window(session: dict, ctx: Context) -> dict:
    win = serving.measure(session, ctx)
    lat, ok = win["latencies_ms"], win["ok"]
    win["end_to_end"] = {
        "query_p50_ms": serving.latency_percentile(lat, ok, 0.50),
        "query_p99_ms": serving.latency_percentile(lat, ok, 0.99),
    }
    win["notes"].update(win["end_to_end"])
    win["notes"]["achieved_qps"] = win["ok_in_window"] / win["window_s"]
    return win


check = serving.check
teardown = serving.teardown


def prove(ctx: Context, controls: bool) -> dict:
    return serving.prove(ctx, controls, window)
