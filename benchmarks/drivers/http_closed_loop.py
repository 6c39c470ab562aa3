"""Driver `http_closed_loop`: capacity. `connections` keep-alive connections
from generator processes of their own, each sending its next query when the
reply to the last has come, against a live `QueryServer` in this process.
`query_qps` is the correct replies that finished inside the window over the
window's seconds."""

from __future__ import annotations

from benchmarks import serving
from benchmarks.harness import Context


rehearsal_env = serving.rehearsal_env
setup = serving.setup


def window(session: dict, ctx: Context) -> dict:
    win = serving.measure(session, ctx)
    win["end_to_end"] = {"query_qps": win["ok_in_window"] / win["window_s"]}
    win["notes"]["query_qps"] = win["end_to_end"]["query_qps"]
    return win


check = serving.check
teardown = serving.teardown


def prove(ctx: Context, controls: bool) -> dict:
    return serving.prove(ctx, controls, window)
